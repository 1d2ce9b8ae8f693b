"""The promise of ``benchmark/specs.py`` (PR 35): a later PR of any kind
adds a configuration, a mix, a cell and per-layer metrics by adding
files and by appending entries at the ends of ``configs``, ``workloads``
and ``per_layer`` in ``BENCHMARK.json``; it edits no file the benchmark
has, turns no check of ``manifest_shape.py`` red, and its new cell
prints every general metric the harness has without having been named
anywhere.

Built here, in the rehearsal's temporary copy, as the next
``model_config`` PR will build it: a configuration (the ``tpch``
generator at the rehearsal's scale under a new name, its own
``expected/`` from ``make_expected.py``), a mix, a cell, a reader
without a constant and one with ``WORKLOADS = [<the new cell>]``, and a
copy of the manifest with one entry appended to ``configs`` and
``workloads`` and two to ``per_layer``.  The shape checks are order-free
both ways, so they pass as well with the two ``per_layer`` entries in
front of the last one (which place the driver accepts is the driver's).

Then the four-chip cell whose files are already there,
``tpch_sf1.mesh_join``, enters the same copy as its ``model_config`` PR
will enter it: by entries alone, one appended to ``workloads`` and the
two readers that wait for it, ``collective_ms`` and
``min_chip_busy_pct``, appended to ``per_layer`` with its name in
``workloads``.  No file of the copy changes; every check that iterates
the manifest, those of ``test_general_metrics.py`` per cell included,
passes in both entry orders; the cell runs traced on four virtual
devices and prints both readers.
XLA:CPU, ``--cpu-rehearsal``, SF0.01, one copy for the whole file.
"""

import copy
import hashlib
import json
import os

import pytest

import bench_rehearsal as rehearsal
import manifest_shape as shape
import test_general_metrics as general
from benchmark import specs

CONFIG = "tpch_next"
MIX = "next_mix"
CELL = f"{CONFIG}.{MIX}"
OLD_CELL = "tpch_sf1.join_agg"
GENERAL, LISTED = "next_statements_per_pass", "next_q06_share_pct"
READER_FILES = {
    GENERAL: '''"""Statements per pass: every cell has them."""

NAME = "next_statements_per_pass"
UNIT = "count"


def read(run):
    return float(len(run.passes[0].queries)) if run.passes else None
''',
    LISTED: f'''"""q06's share of a pass's client time: the new cell's own."""

NAME = "next_q06_share_pct"
UNIT = "%"
WORKLOADS = ["{CELL}"]


def read(run):
    shares = [100.0 * sum(q.client_ms for q in p.queries if q.name == "q06")
              / p.ms for p in run.passes if p.ms]
    return sorted(shares)[len(shares) // 2] if shares else None
''',
}


def _hashes(root: str) -> dict:
    out = {}
    for directory, subdirs, files in os.walk(root):
        subdirs[:] = [d for d in subdirs
                      if d not in ("__pycache__", ".cache", "trace_out")]
        for f in files:
            path = os.path.join(directory, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha1(
                    fh.read()).hexdigest()
    return out


def _write_json(root: str, *parts: str, value) -> None:
    with open(os.path.join(root, *parts), "w") as f:
        json.dump(value, f, indent=1)


def _add_the_next_prs_files(root: str) -> dict:
    """New files only, under ``root``; returns the manifest entries
    that go with them."""
    # the copy's file: already at the rehearsal's scale
    config = specs.read_json(root, "configs", "tpch_sf1.json")
    config["name"] = CONFIG
    config["source"] = "a test's stand-in for the next deployment: " \
        "TPC-H spec rev 2 at the rehearsal's scale, queries Q3 Q6"
    _write_json(root, "configs", CONFIG + ".json", value=config)
    rehearsal.make_expected(root, CONFIG)
    mix = specs.read_json(root, "traffic", "join_agg.json")
    mix.update(name=MIX, queries=["q03", "q06"], what="added by a test")
    _write_json(root, "traffic", MIX + ".json", value=mix)
    why = "added by a test: q3+q6 at the rehearsal's scale, 1 closed-loop client"
    _write_json(root, "workloads", CELL + ".json", value={
        "name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1,
        "why": why})
    for name, text in READER_FILES.items():
        with open(os.path.join(root, "layer_metrics", name + ".py"), "w") as f:
            f.write(text)
    return {
        "configs": [{"name": CONFIG, "source": config["source"],
                     "file": f"benchmark/configs/{CONFIG}.json",
                     "reduced": sorted(config["reduced"]),
                     "why": "added by a test"}],
        "workloads": [{"name": CELL, "config": CONFIG, "traffic": MIX,
                       "chips": 1, "why": why}],
        "per_layer": [
            {"name": GENERAL, "unit": "count", "better": "lower",
             "source": "program_counter", "layer": "Client + protocol",
             "moves": "pass_p50_ms"},
            {"name": LISTED, "unit": "%", "better": "lower",
             "source": "host_clock", "layer": "Client + protocol",
             "moves": "pass_p50_ms", "workloads": [CELL]}],
    }


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    checkout = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench_next")))
    root = os.path.join(checkout, "benchmark")
    parent = shape.load(rehearsal.REPO)
    before = _hashes(root)
    added = _add_the_next_prs_files(root)
    appended = copy.deepcopy(parent)
    for group, entries in added.items():
        appended[group] += entries
    before_last = copy.deepcopy(appended)
    metrics = before_last["per_layer"]
    metrics[-3:] = metrics[-2:] + metrics[-3:-2]
    _write_json(checkout, "BENCHMARK.json", value=appended)
    runs = {cell: rehearsal.last_line(rehearsal.run_cell(
        checkout, cell, trace=1, seconds=3.0)) for cell in (CELL, OLD_CELL)}
    return {"checkout": checkout, "root": root, "parent": parent,
            "appended": appended, "before_last": before_last,
            "before": before, "after": _hashes(root), "added": added,
            "runs": runs}


@pytest.mark.parametrize("placed", ["appended", "before_last"])
def test_every_shape_check_passes_on_the_grown_pair(grown, placed):
    manifest = grown[placed]
    assert shape.cells(manifest)[-1] == CELL
    assert [m["name"] for m in manifest["per_layer"]].index(LISTED) == \
        len(manifest["per_layer"]) - (1 if placed == "appended" else 2)
    shape.every_check(manifest, grown["root"])
    shape.is_general(manifest, grown["root"], GENERAL)
    shape.is_listed_for(manifest, grown["root"], LISTED, CELL)


def test_the_manifest_grew_at_the_ends_and_nowhere_else(grown):
    parent, appended = grown["parent"], grown["appended"]
    assert set(appended) == set(parent)
    for group, entries in grown["added"].items():
        assert appended[group][:len(parent[group])] == parent[group]
        assert appended[group][len(parent[group]):] == entries
    for key in set(parent) - set(grown["added"]):
        assert appended[key] == parent[key]


def test_no_file_the_benchmark_had_was_edited(grown):
    before, after = grown["before"], grown["after"]
    assert {f: h for f, h in after.items() if f in before} == before
    new = sorted(set(after) - set(before))
    assert new == sorted(
        [f"configs/{CONFIG}.json", f"traffic/{MIX}.json",
         f"workloads/{CELL}.json"]
        + [f"layer_metrics/{name}.py" for name in READER_FILES]
        + [f"expected/{CONFIG}/{q}.json"
           for q in ("q01", "q03", "q06", "q14")])


def test_the_new_cell_runs_traced_and_correct(grown):
    result = grown["runs"][CELL]
    assert set(result) == rehearsal.RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert result["compared"]["answers_wrong"]["value"] == 0
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0


def test_the_new_cell_prints_both_of_its_readers(grown):
    metrics = grown["runs"][CELL]["metrics"]
    assert metrics[GENERAL] == {"value": 2.0, "unit": "count"}
    assert metrics[LISTED]["unit"] == "%"
    assert 0 < metrics[LISTED]["value"] < 100


def test_the_new_cell_prints_every_general_metric_an_old_cell_prints(grown):
    """Named nowhere: no reader and no manifest entry lists the cell
    but its own, and it gets what ``tpch_sf1.join_agg`` gets."""
    manifest, root = grown["appended"], grown["root"]
    new, old = (set(grown["runs"][c]["metrics"]) for c in (CELL, OLD_CELL))
    general = {m["name"] for m in manifest["per_layer"]
               if "workloads" not in m}
    assert old & general and old & general <= new
    assert GENERAL in old
    assert {"planning_ms", "protocol_ms", "executor_host_ms",
            "device_busy_ms", "device_idle_pct", "compiles_in_window",
            "host_reads_per_pass", "compact_fallback_pages_per_pass",
            "load_s", "first_call_s"} <= new
    # what it does not print is a mechanism's or waits for several
    # chips, never a general reader that was not told of the cell
    readers = shape.readers(root)
    for name in set(readers) - new:
        only = getattr(readers[name], "WORKLOADS", None)
        assert (only is not None and CELL not in only) \
            or name in shape.WAITING_READERS \
            or name not in old, name


def test_an_old_cell_does_not_print_the_listed_reader(grown):
    assert LISTED not in grown["runs"][OLD_CELL]["metrics"]
    assert grown["runs"][OLD_CELL]["correct"] is True


def _without_its_manifest_list(m):
    del shape.entry_of(m, LISTED)["workloads"]


def _with_a_manifest_list(m):
    shape.entry_of(m, GENERAL)["workloads"] = [CELL]


def _lists_that_differ(m):
    shape.entry_of(m, LISTED)["workloads"] = [OLD_CELL, CELL]


def _a_cell_no_file_defines(m):
    m["workloads"].append(dict(m["workloads"][-1], name="tpch_next.nowhere",
                               traffic="nowhere"))


def _a_reader_no_entry_lists(m):
    m["per_layer"].remove(shape.entry_of(m, GENERAL))


def _a_metric_that_moves_nothing_end_to_end(m):
    shape.entry_of(m, GENERAL)["moves"] = "tokens_per_s"


def _same_cells(metric):
    return lambda m, root: shape.manifest_and_reader_name_the_same_cells(
        m, root, metric)


@pytest.mark.parametrize("spoil,check", [
    (_without_its_manifest_list, _same_cells(LISTED)),
    (_with_a_manifest_list, _same_cells(GENERAL)),
    (_lists_that_differ, _same_cells(LISTED)),
    (_a_cell_no_file_defines, shape.manifest_and_files_name_the_same_things),
    (_a_reader_no_entry_lists, shape.manifest_and_files_name_the_same_things),
    (_a_metric_that_moves_nothing_end_to_end,
     lambda m, root: shape.metric_entry_is_sound(m, root, GENERAL)),
], ids=lambda f: getattr(f, "__name__", "").strip("_") or None)
def test_the_shape_checks_refuse_a_pair_that_disagrees(grown, spoil, check):
    """Each check has been seen to fail: on the grown pair it passes,
    on the pair spoiled in the one way it is there to catch it does
    not, and so the whole does not."""
    check(grown["appended"], grown["root"])
    spoiled = copy.deepcopy(grown["appended"])
    spoil(spoiled)
    with pytest.raises(AssertionError):
        check(spoiled, grown["root"])
    with pytest.raises(AssertionError):
        shape.every_check(spoiled, grown["root"])


# -- the four-chip cell enters by entries alone ------------------------------

MESH_CELL = "tpch_sf1.mesh_join"
#: the waiting readers, each with which way is better
MESH_READERS = {"collective_ms": "lower", "min_chip_busy_pct": "higher"}


def _mesh_entries(root: str) -> dict:
    """The manifest's side of the four-chip cell: its ``workloads``
    entry as its file has it, and the two readers that wait for it."""
    cell = specs.read_json(root, "workloads", MESH_CELL + ".json")
    units = shape.readers(root)
    return {
        "workloads": [{k: cell[k] for k in
                       ("name", "config", "traffic", "chips", "why")}],
        "per_layer": [
            {"name": name, "unit": units[name].UNIT, "better": better,
             "source": "device_trace", "layer": "Mesh tier / exchange",
             "moves": "pass_p50_ms", "workloads": [MESH_CELL]}
            for name, better in MESH_READERS.items()],
    }


@pytest.fixture(scope="module")
def mesh_entered(grown):
    added = _mesh_entries(grown["root"])
    appended = copy.deepcopy(grown["appended"])
    before_last = copy.deepcopy(grown["appended"])
    for group, entries in added.items():
        appended[group] += entries
        before_last[group][-1:-1] = entries
    result = rehearsal.last_line(rehearsal.run_cell(
        grown["checkout"], MESH_CELL, trace=1, devices=4, seconds=3.0))
    return {"appended": appended, "before_last": before_last,
            "added": added, "after": _hashes(grown["root"]),
            "result": result}


@pytest.mark.parametrize("placed", ["appended", "before_last"])
def test_the_mesh_cell_enters_by_entries_alone(grown, mesh_entered,
                                               monkeypatch, placed):
    manifest, root = mesh_entered[placed], grown["root"]
    assert manifest["workloads"][-1 if placed == "appended" else -2][
        "name"] == MESH_CELL
    assert MESH_CELL in shape.MAY_STAND_OUTSIDE  # listed and entered
    # the files, the cells and their chips, every entry and its reader
    shape.every_check(manifest, root)
    for cell in shape.cells(manifest):
        for name in sorted(general.SCOPED):
            general.scope_reader_reports(monkeypatch, name, cell, root)
        general.hbm_roofline_reports(monkeypatch, cell, root)


def test_the_mesh_cell_edits_no_file(grown, mesh_entered):
    assert mesh_entered["after"] == grown["after"]
    parent = grown["appended"]
    for group, entries in mesh_entered["added"].items():
        grew = mesh_entered["appended"][group]
        assert grew[:len(parent[group])] == parent[group]
        assert grew[len(parent[group]):] == entries


def test_the_mesh_cell_runs_traced_with_both_readers(grown, mesh_entered):
    result = mesh_entered["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert set(MESH_READERS) <= set(result["metrics"])
    assert result["metrics"]["min_chip_busy_pct"]["value"] > 0
    # named nowhere else: every general metric an old cell prints
    general_names = {m["name"] for m in mesh_entered["appended"]["per_layer"]
                     if "workloads" not in m}
    old = set(grown["runs"][OLD_CELL]["metrics"])
    assert old & general_names <= set(result["metrics"])


def _entered_twice(m, monkeypatch):
    m["workloads"].append(copy.deepcopy(shape.entry_of(m, MESH_CELL,
                                                       "workloads")))


def _neither_entered_nor_let_stand_outside(m, monkeypatch):
    m["workloads"].remove(shape.entry_of(m, MESH_CELL, "workloads"))
    for name in MESH_READERS:
        m["per_layer"].remove(shape.entry_of(m, name))
    monkeypatch.setattr(shape, "MAY_STAND_OUTSIDE", ())


def _a_waiting_reader_without_its_cells(m, monkeypatch):
    del shape.entry_of(m, "collective_ms")["workloads"]


def _a_waiting_reader_for_a_one_chip_cell(m, monkeypatch):
    shape.entry_of(m, "min_chip_busy_pct")["workloads"].append(OLD_CELL)


def _a_reader_that_does_not_wait_left_out(m, monkeypatch):
    m["per_layer"].remove(shape.entry_of(m, "protocol_ms"))


@pytest.mark.parametrize("spoil,check", [
    (_entered_twice, shape.manifest_and_files_name_the_same_cells),
    (_neither_entered_nor_let_stand_outside,
     shape.manifest_and_files_name_the_same_cells),
    (_a_waiting_reader_without_its_cells, _same_cells("collective_ms")),
    (_a_waiting_reader_for_a_one_chip_cell, _same_cells("min_chip_busy_pct")),
    (_a_reader_that_does_not_wait_left_out,
     shape.manifest_and_files_name_the_same_things),
], ids=lambda f: getattr(f, "__name__", "").strip("_") or None)
def test_the_mesh_rules_refuse_a_pair_that_disagrees(grown, mesh_entered,
                                                      monkeypatch, spoil,
                                                      check):
    """Each rule has been seen to fail, on a copy of the manifest with
    the mesh cell entered: it passes as grown, and not once spoiled."""
    check(mesh_entered["appended"], grown["root"])
    spoiled = copy.deepcopy(mesh_entered["appended"])
    spoil(spoiled, monkeypatch)
    with pytest.raises(AssertionError):
        check(spoiled, grown["root"])
    with pytest.raises(AssertionError):
        shape.every_check(spoiled, grown["root"])
