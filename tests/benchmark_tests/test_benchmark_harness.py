"""The benchmark's harness (BENCHMARK.json, benchmark/run.py): every
cell resolves to its files, the command runs end to end and prints the
contract's last line, refuses to measure without a TPU, and takes a new
cell, mix and per-layer metric as files.  XLA:CPU, ``--cpu-rehearsal``,
SF0.01, two-second windows, one temporary copy for the whole file."""

import json
import os

import pytest

import bench_rehearsal as rehearsal
import manifest_shape as shape
from benchmark import loadgen, specs

MANIFEST = shape.load(rehearsal.REPO)
CELLS = shape.cells(MANIFEST)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_the_four_chip_cell_resolves_entered_or_not():
    """``tpch_sf1.mesh_join``'s files resolve to four chips, the mesh
    and the distributed session, whether the manifest lists the cell
    or lets it stand outside."""
    loaded = specs.load_cell("tpch_sf1.mesh_join")
    assert loaded.chips == 4 and loaded.layout["require_mesh"] is True
    assert loaded.layout["session"] == {"distributed": "true"}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    shape.cell_resolves_to_its_files(MANIFEST, specs.ROOT, cell)


def test_manifest_and_files_name_the_same_things():
    """``shape.MAY_STAND_OUTSIDE`` lets the cell above, whose files are
    there, stand outside the manifest or be entered in it."""
    shape.manifest_and_files_name_the_same_things(MANIFEST, specs.ROOT)


def _asking_for(*chips):
    return {"workloads": [{"chips": c} for c in chips]}


@pytest.mark.parametrize("manifest,taken", [
    (MANIFEST, True),
    (_asking_for(4), True),  # one always may
    (_asking_for(1, 4, 1, 4), True),
    (_asking_for(4, 4), False),
    (_asking_for(1, 4, 4), False),
    (_asking_for(1, 2), False),  # 1 or 4
], ids=["the_manifest", "4", "1414", "44", "144", "12"])
def test_cells_on_four_chips_are_at_most_half_of_the_cells(manifest, taken):
    if taken:
        shape.four_chip_cells_are_at_most_half(manifest)
    else:
        with pytest.raises(AssertionError):
            shape.four_chip_cells_are_at_most_half(manifest)


@pytest.mark.parametrize("metric",
                         [m["name"] for m in MANIFEST["per_layer"]])
def test_every_per_layer_entry_is_sound(metric):
    shape.metric_entry_is_sound(MANIFEST, specs.ROOT, metric)


def test_one_chip_cell_runs_end_to_end(checkout):
    proc = rehearsal.run_cell(checkout, "tpch_sf1.join_agg")
    result = rehearsal.last_line(proc)
    assert set(result) == rehearsal.RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    # XLA:CPU keeps no memory statistics, so no peak_hbm_gb here
    assert set(result["metrics"]) == {"setup_s", "pass_p50_ms", "rows_per_s"}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    observations = json.loads(proc.stdout.strip().splitlines()[-2])
    assert observations["passes"] >= 1
    assert set(observations["per_query_p50"]) == {"q14", "q03"}
    assert observations["window_counters"] == {
        "programs": 0, "persistent_hits": 0, "persistent_misses": 0}


def test_mesh_cell_runs_end_to_end_traced(checkout):
    """Four virtual devices stand in for the four chips; the traced run
    reports the per-layer metrics, the mesh tier's among them."""
    proc = rehearsal.run_cell(checkout, "tpch_sf1.mesh_join", trace=1,
                              devices=4, seconds=3.0)
    result = rehearsal.last_line(proc)
    assert set(result) == rehearsal.RESULT_KEYS | {"breakdown"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    from benchmark import run

    assert set(result["metrics"]) <= {r.NAME for r in run.layer_metric_readers()}
    assert {"planning_ms", "protocol_ms", "executor_host_ms", "device_busy_ms",
            "device_idle_pct", "compiles_in_window", "first_call_s", "load_s",
            "collective_ms", "min_chip_busy_pct"} <= set(result["metrics"])
    assert "hbm_roofline_pct" not in result["metrics"]  # no peak for the CPU
    assert "setup_s" not in result["metrics"]  # end to end: untraced run
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    for entries in result["breakdown"].values():
        assert 1 <= len(entries) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in entries)
    assert any(name.startswith("q14/") for name, _ in
               result["breakdown"]["idle_gaps"])


def test_without_the_flag_and_without_a_tpu_nothing_is_measured(checkout):
    proc = rehearsal.run_cell(checkout, "tpch_sf10.join", rehearsal=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_alone_with_its_manifest_it_exits_nonzero(checkout):
    """In a directory that holds only the benchmark's own files (no
    ``presto_tpu``) the command fails and prints no result."""
    proc = rehearsal.run_cell(checkout, "tpch_sf10.join", pythonpath=None)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_fewer_devices_than_the_cell_asks_for_is_refused(checkout):
    proc = rehearsal.run_cell(checkout, "tpch_sf1.mesh_join", devices=2)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 4 chips" in proc.stderr


def test_a_new_cell_mix_and_metric_are_found_as_files(checkout):
    """What a later PR does: add files, edit none."""
    root = os.path.join(checkout, "benchmark")
    with open(os.path.join(root, "traffic", "two_clients.json"), "w") as f:
        json.dump({"name": "two_clients", "loop": "closed_passes",
                   "clients": 2, "queries": ["q06", "q14"],
                   "rotate_start_by_seed": True,
                   "trailing_spaces": {"base_from_seed_below": 8,
                                       "step_per_statement": 1}}, f)
    with open(os.path.join(root, "workloads", "tpch_sf1.extra.json"), "w") as f:
        json.dump({"name": "tpch_sf1.extra", "config": "tpch_sf1",
                   "traffic": "two_clients", "chips": 1,
                   "why": "added by a test"}, f)
    with open(os.path.join(root, "layer_metrics", "answers.py"), "w") as f:
        f.write("NAME = 'answers'\nUNIT = 'count'\n"
                "WORKLOADS = ['tpch_sf1.extra']\n\n\n"
                "def read(run):\n"
                "    return float(sum(len(p.queries) for p in run.passes))\n")
    result = rehearsal.last_line(
        rehearsal.run_cell(checkout, "tpch_sf1.extra", trace=1))
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["answers"]["unit"] == "count"
    assert result["metrics"]["answers"]["value"] >= 2
    # ... and a cell the new metric does not list leaves it out
    other = rehearsal.last_line(
        rehearsal.run_cell(checkout, "tpch_sf10.join", trace=1))
    assert "answers" not in other["metrics"]
    assert "hbm_roofline_pct" not in other["metrics"]  # no peak for a CPU


def _texts(seed, client=0, n=5):
    cell = specs.load_cell("tpch_sf10.scan_agg")
    s = loadgen.Statements(cell.traffic, cell.queries, seed, client)
    return [q.name for q in s.order], [s.text(q) for _ in range(n)
                                       for q in s.order]


def test_traffic_is_a_function_of_the_seed():
    assert _texts(7) == _texts(7)
    order0, texts0 = _texts(0)
    order1, texts1 = _texts(1)
    assert order0 == ["q06", "q01"] and order1 == ["q01", "q06"]
    assert texts0 != texts1
    # every statement's text is new; only trailing spaces differ
    assert len(set(texts0)) == len(texts0)
    assert len({t.rstrip() for t in texts0}) == 2


def test_closed_loop_counts_only_whole_passes_inside_the_window():
    cell = specs.load_cell("tpch_sf10.scan_agg")
    statements = [loadgen.Statements(cell.traffic, cell.queries, 0)]
    import time

    def submit(client, query, text):
        t0 = time.perf_counter()
        time.sleep(0.03)
        return loadgen.QueryRecord(query.name, 0, 30.0, t0, True)

    seen = []
    t0, passes, records = loadgen.closed_passes(
        cell.traffic, statements, 0.2, submit,
        lambda client, done: seen.append((client, done)))
    assert 2 <= len(passes) <= 4
    assert all(len(p.queries) == 2 and p.end <= t0 + 0.2 for p in passes)
    assert len(records) >= 2 * len(passes)
    assert seen[0] == (0, 0) and [d for _, d in seen] == sorted(d for _, d in seen)
