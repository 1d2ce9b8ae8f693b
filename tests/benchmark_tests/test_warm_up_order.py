"""PR 33: the seed orders the window's passes and not the start of the
process.  Warm-up goes in the mix's own list order for every seed, no
statement text of warm-up and window repeats, the observations carry
the passes' quartiles, the result says what ``correct`` compared, and
the manifest's bounds and lists are sound.  XLA:CPU,
``--cpu-rehearsal``, SF0.01, one temporary copy for the whole file."""

import json
import os
import statistics
import subprocess
import sys

import pytest

import bench_rehearsal as rehearsal
from benchmark import loadgen, run, specs, stats

with open(os.path.join(rehearsal.REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CELL = "tpch_sf1.join_agg"
SEEDS = {"odd": 2147483999, "even": 2147483998}

#: ``benchmark/run.py`` as it is, with every text ``Statements.text``
#: hands out written to standard error in the order it was asked for
RECORDING = '''
import json, runpy, sys
sys.path.insert(0, ".")
from benchmark import loadgen
texts, original = [], loadgen.Statements.text
def text(self, query):
    t = original(self, query)
    texts.append([query.name, len(t) - len(t.rstrip(" "))])
    return t
loadgen.Statements.text = text
sys.argv[0] = "benchmark/run.py"
try:
    runpy.run_path("benchmark/run.py", run_name="__main__")
finally:
    print("TEXTS " + json.dumps(texts), file=sys.stderr, flush=True)
'''


@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """One untraced run of ``tpch_sf1.join_agg`` for each parity of the
    seed: (observations, result, texts asked for, standard error)."""
    checkout = rehearsal.make_copy(str(tmp_path_factory.mktemp("bench_warm")))
    out = {}
    for parity, seed in SEEDS.items():
        proc = subprocess.run(
            [sys.executable, "-c", RECORDING, "--workload", CELL,
             "--seed", str(seed), "--seconds", "2", "--trace", "0",
             "--cpu-rehearsal"],
            cwd=checkout, env=rehearsal.env(), capture_output=True,
            text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        lines = proc.stdout.strip().splitlines()
        texts = next(json.loads(line[len("TEXTS "):])
                     for line in proc.stderr.splitlines()
                     if line.startswith("TEXTS "))
        out[parity] = (json.loads(lines[-2]), json.loads(lines[-1]), texts,
                       proc.stderr)
    return out


@pytest.mark.parametrize("parity", sorted(SEEDS))
def test_warm_up_goes_in_the_mixs_list_order_whatever_the_seed(rehearsed,
                                                               parity):
    observations, result, texts, _ = rehearsed[parity]
    assert result["correct"] is True and result["failed"] == 0
    assert observations["warm_up_order"] == ["q14", "q03"]
    # each query twice, in that order, before the window's first text
    assert [name for name, _ in texts[:4]] == ["q14", "q14", "q03", "q03"]


def test_the_seed_still_orders_the_windows_passes(rehearsed):
    odd, even = rehearsed["odd"][0], rehearsed["even"][0]
    assert odd["warm_up_order"] == even["warm_up_order"]
    assert odd["queries_per_pass"] == ["q03", "q14"]
    assert even["queries_per_pass"] == ["q14", "q03"]
    for parity in SEEDS:
        observations, _, texts, _ = rehearsed[parity]
        window = [name for name, _ in texts[4:]]
        order = observations["queries_per_pass"]
        assert window[:4] == order + order


@pytest.mark.parametrize("parity", sorted(SEEDS))
def test_no_statement_text_of_warm_up_and_window_repeats(rehearsed, parity):
    observations, result, texts, _ = rehearsed[parity]
    # warm-up's four and every statement the window attempted
    assert len(texts) == 4 + result["attempted"]
    assert len({(name, spaces) for name, spaces in texts}) == len(texts)
    # a query's n-th text carries base + n spaces, warm-up counted in
    base = SEEDS[parity] % 64
    for name in ("q14", "q03"):
        spaces = [s for n, s in texts if n == name]
        assert spaces == list(range(base, base + len(spaces)))


@pytest.mark.parametrize("parity", sorted(SEEDS))
def test_observations_carry_the_passes_quartiles(rehearsed, parity):
    pass_ms = rehearsed[parity][0]["pass_ms"]
    assert {"p25", "p50", "p75", "min", "max", "p95"} <= set(pass_ms)
    assert pass_ms["min"] <= pass_ms["p25"] <= pass_ms["p50"] \
        <= pass_ms["p75"] <= pass_ms["max"]


@pytest.mark.parametrize("parity", sorted(SEEDS))
def test_the_result_says_what_correct_compared(rehearsed, parity):
    _, result, _, stderr = rehearsed[parity]
    assert list(result)[-1] == "compared"
    compared = result["compared"]
    assert compared["answers_wrong"] == {
        "value": 0, "limit": 0, "of": 4 + result["attempted"]}
    assert compared["passes"]["value"] >= compared["passes"]["at_least"] == 1
    assert compared["first_wrong"] == []
    last = [line for line in stderr.splitlines()
            if not line.startswith("TEXTS ")][-2:]
    assert all(line.startswith("compared: ") for line in last)
    assert "answers_wrong 0 (limit 0)" in last[0]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cells_warm_up_order_is_its_mixs_list(cell):
    """What ``run.py`` warms up in (``cell.queries``) is the mix's list
    as written, for every seed; only ``Statements.order`` rotates."""
    loaded = specs.load_cell(cell)
    listed = loaded.traffic["queries"]
    assert [q.name for q in loaded.queries] == listed
    orders = {seed: [q.name for q in loadgen.Statements(
        loaded.traffic, loaded.queries, seed).order] for seed in (6, 7)}
    assert sorted(orders[6]) == sorted(orders[7]) == sorted(listed)
    assert orders[6][0] == listed[6 % len(listed)]
    assert orders[7][0] == listed[7 % len(listed)]


@pytest.mark.parametrize("values", [[3.0, 1.0], [5.0, 1.0, 2.0, 9.0, 4.0],
                                    list(range(1, 83))])
def test_quartiles_are_pythons(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.quartiles(values) == (q1, q3)


def test_quartiles_of_one_reading_or_none():
    assert stats.quartiles([]) is None and stats.quartiles([7.0]) is None


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["end_to_end"]])
def test_every_bound_is_a_positive_share(metric):
    entry = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric)
    assert isinstance(entry["bound"], float)
    assert 0.01 <= entry["bound"] <= 0.25
    assert entry["source"] in ("host_clock", "device_trace")
    assert "workloads" not in entry  # every cell reports every one


LISTED = [m["name"] for m in MANIFEST["per_layer"] if "workloads" in m]


@pytest.mark.parametrize("metric", LISTED)
def test_manifest_and_reader_name_the_same_cells(metric):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert set(entry["workloads"]) <= set(CELLS)
    assert len(set(entry["workloads"])) == len(entry["workloads"])
    reader = next(r for r in run.layer_metric_readers() if r.NAME == metric)
    only = getattr(reader, "WORKLOADS", None)
    if only is None:
        # the reader decides by what it finds (``hbm_roofline_pct``:
        # one-chip cells with a peak on record)
        assert metric == "hbm_roofline_pct"
    else:
        assert only == entry["workloads"]


def test_the_fkjoin_cell_is_listed_for_the_join_and_sort_metrics():
    listed = {m["name"]: m.get("workloads") for m in MANIFEST["per_layer"]}
    for name in ("op_join_probe_ms", "op_join_build_ms", "agg_sort_ms",
                 "hbm_roofline_pct"):
        assert "tpch_sf1_fkjoin.csr_join" in listed[name], name
