"""``compact_fallback_pages_per_pass`` (benchmark/layer_metrics/, PR 31):
the miss rate of the chains that compact, from the statement stats;
on synthetic passes, in the manifest, and in the CPU rehearsal of
``tpch_sf1.join_agg``, where q14 compacts in front of its probe and q3
inside it and neither misses."""

import json
import os

import pytest

import bench_rehearsal as rehearsal
from benchmark import loadgen, run as bench_run, specs

NAME = "compact_fallback_pages_per_pass"
READER = {r.NAME: r for r in bench_run.layer_metric_readers()}[NAME]


def _run(per_pass):
    """A ``run.Run`` of passes whose statements carry these stats."""
    passes = [
        loadgen.Pass(0, i, 0.0, [
            loadgen.QueryRecord(f"q{j}", 10 * i + j, 0.0, 0.0, True,
                                stats=dict(stats))
            for j, stats in enumerate(statements)])
        for i, statements in enumerate(per_pass)]
    return bench_run.Run(specs.load_cell("tpch_sf1.join_agg"), {}, None, {},
                         {}, passes, {})


@pytest.mark.parametrize("per_pass,want", [
    # every estimate held
    ([[{"compactedPages": 6, "compactFallbackPages": 0}] * 2] * 3, 0),
    # summed over a pass's statements, the median over passes
    ([[{"compactFallbackPages": 6}, {"compactFallbackPages": 1}],
      [{"compactFallbackPages": 0}, {"compactFallbackPages": 0}],
      [{"compactFallbackPages": 6}, {"compactFallbackPages": 0}]], 6),
    # a statement without the counter (a result-cache hit) counts nothing
    ([[{"compactFallbackPages": 2}, {"executionMs": 1.0}]], 2),
    # a program from before the counter says nothing at all
    ([[{"executionMs": 1.0}, {"hostReads": 3}]], None),
])
def test_reader_sums_a_pass_and_takes_the_median(per_pass, want):
    assert READER.read(_run(per_pass)) == want


def test_manifest_lists_it_for_every_cell():
    with open(os.path.join(rehearsal.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = manifest["per_layer"][-1]
    assert entry == {"name": NAME, "unit": READER.UNIT, "better": "lower",
                     "source": "program_counter", "layer": "Executor",
                     "moves": "pass_p50_ms"}
    assert getattr(READER, "WORKLOADS", None) is None


def test_cpu_rehearsal_compacts_both_queries_and_misses_nothing(
        tmp_path_factory):
    checkout = rehearsal.make_copy(
        str(tmp_path_factory.mktemp("bench_compact")))
    proc = rehearsal.run_cell(checkout, "tpch_sf1.join_agg", trace=1,
                              seconds=3.0)
    result = rehearsal.last_line(proc)
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"][NAME] == {"value": 0, "unit": "count"}
    programs = {name.split("/")[0]
                for name, _ in result["breakdown"]["device_ops"]}
    assert "jit_chain_leaf_filter_compact_probe_agg_k0a2" in programs
    assert "jit_chain_leaf_filter_probe_agg_k3a1_compact_in_probe0" in programs
