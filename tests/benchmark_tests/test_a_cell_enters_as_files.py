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
``workloads``.  Where the repo's manifest lists the cell already, its
entries are the ones that name it and nothing is appended twice.  No
file of the copy changes; every check that iterates the manifest, those
of ``test_general_metrics.py`` per cell included, passes in both entry
orders; the cell runs traced on four virtual devices and prints both
readers.

Last, the entered state itself: a file copy of the grown copy whose own
``BENCHMARK.json`` has the cell entered, with a reader of its own
mechanism besides the two waiting ones, stands for the repo's manifest
once that PR is in, and every check and the same placement of the cell
pass on it too.
XLA:CPU, ``--cpu-rehearsal``, SF0.01, one rehearsal copy for the whole
file.
"""

import copy
import hashlib
import json
import os
import shutil

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


def _names_the_cell(entry: dict) -> bool:
    """A ``workloads`` entry of the cell, or a ``per_layer`` entry whose
    ``workloads`` lists it."""
    return entry["name"] == MESH_CELL or MESH_CELL in entry.get(
        "workloads", ())


def _mesh_placements(manifest: dict, root: str) -> dict:
    """The four-chip cell entered into ``manifest``, whatever its state.
    Where the manifest lists the cell, ``added`` is every entry that
    names it, the waiting readers and any reader of its own mechanism,
    and ``appended`` is the manifest as it stands; where it does not,
    ``added`` is ``_mesh_entries`` and ``appended`` the manifest with
    them at the ends.  ``without`` has no entry that names the cell,
    and ``before_last`` is ``without`` with ``added`` one place in from
    the end of each group."""
    entered = MESH_CELL in shape.cells(manifest)
    if entered:
        added = {group: [copy.deepcopy(e) for e in manifest[group]
                         if _names_the_cell(e)]
                 for group in ("workloads", "per_layer")}
    else:
        added = _mesh_entries(root)
    without = copy.deepcopy(manifest)
    for group, entries in added.items():
        without[group] = [e for e in without[group] if e not in entries]
    appended = copy.deepcopy(manifest)
    before_last = copy.deepcopy(without)
    for group, entries in added.items():
        if not entered:
            appended[group] += copy.deepcopy(entries)
        before_last[group][-1:-1] = copy.deepcopy(entries)
    return {"entered": entered, "added": added, "without": without,
            "appended": appended, "before_last": before_last}


@pytest.fixture(scope="module")
def mesh_entered(grown):
    placed = _mesh_placements(grown["appended"], grown["root"])
    result = rehearsal.last_line(rehearsal.run_cell(
        grown["checkout"], MESH_CELL, trace=1, devices=4, seconds=3.0))
    return {**placed, "after": _hashes(grown["root"]), "result": result}


def _enters_in_every_check(monkeypatch, manifest: dict, root: str) -> None:
    """The files, the cells and their chips, every entry and its
    reader, and each cell's scope and roofline reports."""
    shape.every_check(manifest, root)  # none entered twice among them
    assert MESH_CELL in shape.cells(manifest)
    assert MESH_CELL in shape.MAY_STAND_OUTSIDE  # listed and entered
    for cell in shape.cells(manifest):
        for name in sorted(general.SCOPED):
            general.scope_reader_reports(monkeypatch, name, cell, root)
        general.hbm_roofline_reports(monkeypatch, cell, root)


@pytest.mark.parametrize("placed", ["appended", "before_last"])
def test_the_mesh_cell_enters_by_entries_alone(grown, mesh_entered,
                                               monkeypatch, placed):
    manifest = mesh_entered[placed]
    names = shape.cells(manifest)
    if placed == "before_last":
        assert names[-2] == MESH_CELL
    elif mesh_entered["entered"]:
        # where the repo's manifest has it, after its own PR's append
        assert manifest == grown["appended"]
    else:
        assert names[-1] == MESH_CELL
    _enters_in_every_check(monkeypatch, manifest, grown["root"])


def _is_without_and_added(placed: dict) -> None:
    """Each placement is the manifest without the cell plus the cell's
    entries, in their order, and nothing else."""
    without, added = placed["without"], placed["added"]
    for key in ("appended", "before_last"):
        manifest = placed[key]
        assert set(manifest) == set(without)
        for group, value in manifest.items():
            entries = added.get(group)
            if entries is None:
                assert value == without[group], group
                continue
            assert [e for e in value if e not in entries] == without[group]
            assert [e for e in value if e in entries] == entries


def test_the_mesh_cell_edits_no_file(grown, mesh_entered):
    assert mesh_entered["after"] == grown["after"]
    if not mesh_entered["entered"]:
        assert mesh_entered["without"] == grown["appended"]
    _is_without_and_added(mesh_entered)


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
    for group in ("workloads", "per_layer"):
        m[group] = [e for e in m[group] if not _names_the_cell(e)]
    monkeypatch.setattr(shape, "MAY_STAND_OUTSIDE", ())


def _a_waiting_reader_without_its_cells(m, monkeypatch):
    del shape.entry_of(m, "collective_ms")["workloads"]


def _a_waiting_reader_for_a_one_chip_cell(m, monkeypatch):
    shape.entry_of(m, "min_chip_busy_pct")["workloads"].append(OLD_CELL)


def _a_reader_that_does_not_wait_left_out(m, monkeypatch):
    m["per_layer"].remove(shape.entry_of(m, "protocol_ms"))


MESH_SPOILS = pytest.mark.parametrize("spoil,check", [
    (_entered_twice, shape.manifest_and_files_name_the_same_cells),
    (_neither_entered_nor_let_stand_outside,
     shape.manifest_and_files_name_the_same_cells),
    (_a_waiting_reader_without_its_cells, _same_cells("collective_ms")),
    (_a_waiting_reader_for_a_one_chip_cell, _same_cells("min_chip_busy_pct")),
    (_a_reader_that_does_not_wait_left_out,
     shape.manifest_and_files_name_the_same_things),
], ids=lambda f: getattr(f, "__name__", "").strip("_") or None)


def _refuses(monkeypatch, manifest: dict, root: str, spoil, check) -> None:
    """The rule passes on ``manifest`` and not once it is spoiled, and
    so neither does the whole."""
    check(manifest, root)
    spoiled = copy.deepcopy(manifest)
    spoil(spoiled, monkeypatch)
    with pytest.raises(AssertionError):
        check(spoiled, root)
    with pytest.raises(AssertionError):
        shape.every_check(spoiled, root)


@MESH_SPOILS
def test_the_mesh_rules_refuse_a_pair_that_disagrees(grown, mesh_entered,
                                                      monkeypatch, spoil,
                                                      check):
    """Each rule has been seen to fail, on a copy of the manifest with
    the mesh cell entered: it passes as grown, and not once spoiled."""
    _refuses(monkeypatch, mesh_entered["appended"], grown["root"], spoil,
             check)


# -- the entered state, rehearsed ---------------------------------------------

#: stands for the readers of its own mechanism the cell's PR brings
MECHANISM = "next_mesh_statements_per_pass"
MECHANISM_READER = f'''"""The four-chip cell's own: statements per pass."""

NAME = "{MECHANISM}"
UNIT = "count"
WORKLOADS = ["{MESH_CELL}"]


def read(run):
    return float(len(run.passes[0].queries)) if run.passes else None
'''


def _copy_with_the_mechanism(source: str, dest: str, leave_out=()) -> None:
    """``source`` to ``dest`` as files, without what runs leave behind
    and without ``leave_out`` (paths relative to ``source``), and with
    the mechanism's reader added."""
    def ignore(directory, names):
        return [n for n in names
                if n in ("__pycache__", ".cache", "trace_out")
                or os.path.relpath(os.path.join(directory, n), source)
                in leave_out]

    shutil.copytree(source, dest, ignore=ignore)
    with open(os.path.join(dest, "layer_metrics", MECHANISM + ".py"),
              "w") as f:
        f.write(MECHANISM_READER)


@pytest.fixture(scope="module")
def entered(grown, tmp_path_factory):
    """The repo once the cell's PR is in, made from the rehearsal's copy
    by file copies: ``repo_root`` holds the copy's files without the
    next PR's, and ``repo`` a ``BENCHMARK.json`` with the cell entered
    and one reader of its own mechanism appended; ``root`` and
    ``grown`` add the next PR's files and entries to them as the
    ``grown`` fixture adds them to the repo, and ``placed`` is the
    four-chip half's placement of the cell in that grown manifest."""
    new = set(grown["after"]) - set(grown["before"])
    repo = str(tmp_path_factory.mktemp("bench_entered"))
    repo_root = os.path.join(repo, "benchmark")
    _copy_with_the_mechanism(grown["root"], repo_root, leave_out=new)
    manifest = _mesh_placements(grown["parent"], repo_root)["appended"]
    manifest["per_layer"].append({
        "name": MECHANISM, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Mesh tier / exchange",
        "moves": "pass_p50_ms", "workloads": [MESH_CELL]})
    _write_json(repo, "BENCHMARK.json", value=manifest)
    root = os.path.join(str(tmp_path_factory.mktemp("bench_entered_next")),
                        "benchmark")
    _copy_with_the_mechanism(grown["root"], root)
    appended = shape.load(repo)
    for group, entries in grown["added"].items():
        appended[group] += copy.deepcopy(entries)
    return {"repo": shape.load(repo), "repo_root": repo_root,
            "grown": appended, "root": root,
            "placed": _mesh_placements(appended, root)}


@pytest.mark.parametrize("state", ["repo", "grown", "appended",
                                   "before_last"])
def test_the_entered_state_passes_every_check(entered, monkeypatch, state):
    """With the cell already entered, the repo's manifest, the next
    PR's growth of it and both placements of the cell in that growth
    pass every check; the placements append nothing twice: what names
    the cell is the cell's entry, both waiting readers, the mechanism's
    reader and whatever readers of its own the repo lists, each once."""
    placed = entered["placed"]
    if state == "repo":
        manifest, root = entered["repo"], entered["repo_root"]
    else:
        manifest = entered["grown"] if state == "grown" else placed[state]
        root = entered["root"]
    _enters_in_every_check(monkeypatch, manifest, root)
    shape.is_listed_for(manifest, root, MECHANISM, MESH_CELL)
    for name in MESH_READERS:
        shape.manifest_and_reader_name_the_same_cells(manifest, root, name)
    if state != "repo":
        assert shape.cells(manifest)[-1] == CELL
    if state in ("appended", "before_last"):
        assert placed["entered"]
        assert [e["name"] for e in placed["added"]["workloads"]] == \
            [MESH_CELL]
        names = [e["name"] for e in placed["added"]["per_layer"]]
        assert len(set(names)) == len(names)
        assert set(names) >= {*MESH_READERS, MECHANISM}
        _is_without_and_added(placed)
        if state == "appended":
            assert manifest == entered["grown"]
        else:
            assert shape.cells(manifest)[-2] == MESH_CELL


@MESH_SPOILS
def test_the_entered_state_refuses_a_pair_that_disagrees(entered,
                                                         monkeypatch, spoil,
                                                         check):
    """The same rules, each seen to fail on the grown manifest in which
    the cell stood entered before the next PR came."""
    _refuses(monkeypatch, entered["grown"], entered["root"], spoil, check)
