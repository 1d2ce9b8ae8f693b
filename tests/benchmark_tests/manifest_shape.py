"""What ``BENCHMARK.json`` and the files under ``benchmark/`` owe each
other, as functions of ``(manifest, benchmark root)``: the tests of this
directory call them on the repo's pair, and
``test_a_cell_enters_as_files.py`` on a temporary copy to which a later
PR's files and entries were added.

None depends on where an entry stands in its list or on how many
entries there are: a later PR appends a configuration, a cell and
per-layer metrics, edits no file under the manifest's ``paths``, and
every check here still passes (``benchmark/specs.py`` has the rule).
That holds for the cells that may stand outside the manifest and the
readers that may wait for a cell on several chips, below: entering one
is an appended entry, and nothing here is edited for it.
"""

import json
import os
import re

from benchmark import loadgen, run, specs

#: cells defined as files and run by these tests that may stand outside
#: the manifest: on the chip a statement of ``tpch_sf1.mesh_join`` takes
#: 15-26 s while the host re-traces the mesh programs (PERF.md section 4).
#: Entering one is an appended ``workloads`` entry and needs no edit
#: here: a cell listed here and entered passes too
MAY_STAND_OUTSIDE = ("tpch_sf1.mesh_join",)
#: readers that may wait for a cell on several chips: absent from
#: ``per_layer`` until one is entered; once listed, their entry names in
#: ``workloads`` only manifest cells on two chips or more (the reader
#: itself carries no list: it says nothing on one chip)
WAITING_READERS = ("collective_ms", "min_chip_busy_pct")
#: what every cell reports end to end
END_TO_END = {"pass_p50_ms", "rows_per_s", "peak_hbm_gb", "setup_s"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")


def load(checkout: str) -> dict:
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        return json.load(f)


def cells(manifest: dict) -> list:
    return [w["name"] for w in manifest["workloads"]]


def entry_of(manifest: dict, name: str, group: str = "per_layer") -> dict:
    """The one entry of ``group`` with this name, wherever it stands."""
    found = [m for m in manifest[group] if m["name"] == name]
    assert len(found) == 1, f"{name}: {len(found)} entries in {group}"
    return found[0]


def readers(root: str) -> dict:
    return {r.NAME: r for r in run.layer_metric_readers(root)}


def cell_resolves_to_its_files(manifest: dict, root: str, cell: str) -> None:
    entry = entry_of(manifest, cell, "workloads")
    loaded = specs.load_cell(cell, root)
    assert loaded.config["name"] == entry["config"]
    assert loaded.traffic["name"] == entry["traffic"]
    assert loaded.chips == entry["chips"]
    assert loaded.traffic["loop"] in loadgen.LOOPS
    assert loaded.queries and all(q.sql and q.expected for q in loaded.queries)
    config = entry_of(manifest, entry["config"], "configs")
    directory = os.path.basename(root)
    assert config["file"] == f"{directory}/configs/{entry['config']}.json"
    assert config["source"] == loaded.config["source"]
    assert sorted(config["reduced"]) == sorted(loaded.config["reduced"])


def manifest_and_files_name_the_same_cells(manifest: dict,
                                            root: str) -> None:
    """Every manifest cell has its files; every cell with files is in
    the manifest or may stand outside it; no cell is named twice."""
    entered, defined = cells(manifest), set(specs.cell_names(root))
    assert len(set(entered)) == len(entered), "a cell entered twice"
    assert set(entered) <= defined, "an entered cell without its files"
    assert set(MAY_STAND_OUTSIDE) <= defined, \
        "a cell let stand outside without its files"
    assert defined <= set(entered) | set(MAY_STAND_OUTSIDE), \
        "a cell with files, neither entered nor let stand outside"


def waiting_reader_names_mesh_cells(manifest: dict, metric: str) -> None:
    """A waiting reader that is listed names, in ``workloads``, only
    manifest cells on two chips or more."""
    chips = {w["name"]: w["chips"] for w in manifest["workloads"]}
    listed = entry_of(manifest, metric).get("workloads")
    assert listed, f"{metric}: a waiting reader's entry lists its cells"
    assert len(set(listed)) == len(listed)
    assert all(chips.get(c, 0) >= 2 for c in listed), listed


def manifest_and_files_name_the_same_things(manifest: dict,
                                            root: str) -> None:
    manifest_and_files_name_the_same_cells(manifest, root)
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    assert manifest["paths"] == ["benchmark", "tests/benchmark_tests"]
    listed = {m["name"]: m["unit"] for m in manifest["per_layer"]}
    assert len(listed) == len(manifest["per_layer"])  # no name twice
    waiting = set(WAITING_READERS) - set(listed)
    found = {n: r.UNIT for n, r in readers(root).items() if n not in waiting}
    assert found == listed
    end_to_end = {m["name"] for m in manifest["end_to_end"]}
    assert END_TO_END <= end_to_end
    assert {m["moves"] for m in manifest["per_layer"]} <= end_to_end
    # every configuration is some cell's, under a name of its own
    configs = [c["name"] for c in manifest["configs"]]
    assert sorted(set(configs)) == sorted(
        {w["config"] for w in manifest["workloads"]})
    assert len(set(configs)) == len(configs)
    # a configuration under a mix is one cell: the pair is given once,
    # the cells standing outside included
    pairs = [(c["config"], c["traffic"]) for c in (
        specs.read_json(root, "workloads", n + ".json")
        for n in specs.cell_names(root))]
    assert len(set(pairs)) == len(pairs)


def four_chip_cells_are_at_most_half(manifest: dict) -> None:
    """The contract's rule: every cell takes 1 or 4 chips; of the
    cells at most half, rounded down, may ask for four, and one always
    may."""
    chips = [w["chips"] for w in manifest["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


def bound_is_a_positive_share(manifest: dict, metric: str) -> None:
    entry = entry_of(manifest, metric, "end_to_end")
    assert isinstance(entry["bound"], float)
    assert 0.01 <= entry["bound"] <= 0.25
    assert entry["source"] in ("host_clock", "device_trace")
    if metric in END_TO_END:
        assert "workloads" not in entry  # every cell reports these
    else:
        assert set(entry.get("workloads", ())) <= set(cells(manifest))


def metric_entry_is_sound(manifest: dict, root: str, metric: str) -> None:
    """One per-layer entry against the contract's form and its reader."""
    entry = entry_of(manifest, metric)
    assert set(entry) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["unit"] == readers(root)[metric].UNIT
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
    assert 1 <= len(entry["layer"]) <= 200 and "\n" not in entry["layer"]
    moved = entry_of(manifest, entry["moves"], "end_to_end")
    # the cells that report it report what it moves
    assert set(entry.get("workloads", cells(manifest))) <= set(
        moved.get("workloads", cells(manifest)))


def manifest_and_reader_name_the_same_cells(manifest: dict, root: str,
                                            metric: str) -> None:
    """A general metric has no list in either place; a mechanism's has
    the same list in both, of cells the manifest has, each once; a
    waiting reader's list is in the manifest alone, of cells on several
    chips."""
    listed = entry_of(manifest, metric).get("workloads")
    if metric in WAITING_READERS:
        assert getattr(readers(root)[metric], "WORKLOADS", None) is None
        waiting_reader_names_mesh_cells(manifest, metric)
        return
    assert listed == getattr(readers(root)[metric], "WORKLOADS", None)
    if listed is not None:
        assert listed and set(listed) <= set(cells(manifest))
        assert len(set(listed)) == len(listed)


def is_general(manifest: dict, root: str, metric: str) -> None:
    """No list of cells in reader or manifest: it reports in every
    cell, those later PRs add too."""
    manifest_and_reader_name_the_same_cells(manifest, root, metric)
    assert "workloads" not in entry_of(manifest, metric)


def is_listed_for(manifest: dict, root: str, metric: str, cell: str) -> None:
    """A mechanism's metric names ``cell``, among whatever later PRs
    appended, in reader and manifest alike."""
    manifest_and_reader_name_the_same_cells(manifest, root, metric)
    assert cell in entry_of(manifest, metric)["workloads"]


def every_check(manifest: dict, root: str) -> None:
    """All of the above over every entry the manifest has."""
    manifest_and_files_name_the_same_things(manifest, root)
    four_chip_cells_are_at_most_half(manifest)
    for cell in cells(manifest):
        cell_resolves_to_its_files(manifest, root, cell)
    for m in manifest["end_to_end"]:
        bound_is_a_positive_share(manifest, m["name"])
    for m in manifest["per_layer"]:
        metric_entry_is_sound(manifest, root, m["name"])
        manifest_and_reader_name_the_same_cells(manifest, root, m["name"])
