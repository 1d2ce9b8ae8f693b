"""Cold-start compile budget: the shape-canonicalizing program
registry (exec/programs.py) must keep distinct compiled XLA programs
bounded and reuse compiled binaries across program registries and
processes (the persistent cache).  These tests pin the budgets so a
future PR that re-fragments shapes — a stray data-dependent capacity,
a signature that stops matching — fails loudly instead of silently
re-paying the cold-start tax (VERDICT checklist #1)."""

import jax
import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.exec.programs import (
    ProgramRegistry, default_registry, ir_signature,
    persistent_cache_stats,
)
from presto_tpu.runner import QueryRunner
from tests.tpch_queries import QUERIES


def _fresh_runner(sf=0.01):
    from presto_tpu.connectors.tpch import Tpch

    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=sf))
    registry = ProgramRegistry()
    return QueryRunner(catalog, programs=registry), registry


# measured 8 distinct programs for cold q1+q6 at sf 0.01 (chain +
# fold/final per aggregation, projection chain, sort); the pin leaves
# two programs of headroom for planner drift, not for fragmentation
Q1_Q6_PROGRAM_BUDGET = 10


def test_cold_q1_q6_program_budget():
    runner, registry = _fresh_runner()
    runner.execute(QUERIES[1])
    runner.execute(QUERIES[6])
    progs = registry.program_count()
    assert 0 < progs <= Q1_Q6_PROGRAM_BUDGET, (
        f"cold q1+q6 compiled {progs} distinct programs "
        f"(budget {Q1_Q6_PROGRAM_BUDGET}): shapes re-fragmented")


def test_structural_twin_query_shares_programs():
    """A structurally identical query (different SQL text, fresh plan
    nodes) must be a 100% registry hit — zero new programs."""
    runner, registry = _fresh_runner()
    sql = ("SELECT l_returnflag, sum(l_quantity) FROM lineitem "
           "GROUP BY l_returnflag")
    runner.execute(sql)
    before = registry.program_count()
    misses_before = registry.misses
    runner.execute(sql + "  ")  # distinct text -> plan cache miss
    assert registry.program_count() == before
    assert registry.misses == misses_before
    assert registry.hits > 0


def test_rebuilt_executor_keeps_programs():
    """SET SESSION rebuilds the executor; compiled programs survive in
    the shared registry (the seed recompiled everything)."""
    runner, registry = _fresh_runner()
    sql = "SELECT sum(l_quantity) FROM lineitem WHERE l_discount < 0.05"
    runner.execute(sql)
    before = registry.program_count()
    runner.execute("SET SESSION distributed_sort = false")
    runner.execute(sql)
    assert registry.program_count() == before


def test_explain_analyze_verbose_reports_registry():
    runner, _ = _fresh_runner()
    res = runner.execute(
        "EXPLAIN ANALYZE VERBOSE SELECT count(*) FROM nation")
    text = res.rows[0][0]
    assert "program registry:" in text
    assert "hits" in text and "misses" in text and "compile" in text
    assert "compiled XLA programs:" in text


def test_persistent_cache_second_registry_hits(tmp_path):
    """A second registry (fresh jit caches, same cache dir) must
    rehydrate serialized XLA binaries: persistent hits recorded and
    the programs recompile from disk, not from scratch."""
    from jax.experimental.compilation_cache import compilation_cache

    # the engine never moves the cache once a runner exists, so the
    # test points jax at its temporary directory itself; jax binds its
    # cache object to a directory once, hence the resets
    suite_dir = jax.config.jax_compilation_cache_dir
    cache_dir = str(tmp_path / "xla-cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()
    try:
        runner, _ = _fresh_runner()
        cold = persistent_cache_stats()
        assert cold["dir"] == cache_dir
        runner.execute("SELECT sum(n_regionkey) FROM nation")
        jax.clear_caches()  # drop in-process executables, keep disk
        stats0 = persistent_cache_stats()
        # every cold compile was written to the empty directory
        assert stats0["persistent_misses"] > cold["persistent_misses"]
        runner2, reg2 = _fresh_runner()
        runner2.execute("SELECT sum(n_regionkey) FROM nation")
        assert (persistent_cache_stats()["persistent_hits"]
                > stats0["persistent_hits"])
        assert reg2.program_count() > 0
    finally:
        jax.config.update("jax_compilation_cache_dir", suite_dir)
        compilation_cache.reset_cache()


def test_ir_signature_distinguishes_lossy_reprs():
    """Type repr hides the dictionary flag; signatures must not."""
    from presto_tpu.types import VARCHAR, VarcharType

    raw = VarcharType(16, raw=True)
    assert ir_signature(VARCHAR) != ir_signature(raw)
    assert ir_signature(VARCHAR) == ir_signature(VARCHAR)


def test_ir_signature_dictionary_identity():
    from presto_tpu.page import Dictionary

    d1 = Dictionary(["a", "b"])
    d2 = Dictionary(["a", "b"])
    assert ir_signature(d1) == ir_signature(d1)
    assert ir_signature(d1) != ir_signature(d2)  # identity, not content


def test_default_registry_is_shared():
    assert default_registry() is default_registry()


def test_stage_signature_sensitivity():
    """What a query's text changes in a chain's program flips the
    chain's signature (the registry's correctness guarantee; field by
    field in tests/test_chain_lowering.py); equal structure must sign
    equal across separately planned queries."""
    runner, _ = _fresh_runner()
    ex = runner.executor

    def sig(sql):
        plan = runner.binder.plan(sql)
        # walk to the streaming chain root (under the Output node)
        node = plan
        while not ex._lower(node).stages and node.sources:
            node = node.sources[0]
        return ir_signature(ex._lower(node).signature())

    base = "SELECT l_quantity FROM lineitem WHERE l_discount < 0.05"
    assert sig(base) == sig(base.replace("0.05", "0.05"))
    assert sig(base) != sig(base.replace("0.05", "0.06"))  # predicate
    assert sig(base) != sig(base.replace("l_quantity", "l_tax"))  # proj
    agg = ("SELECT l_returnflag, sum(l_quantity) FROM lineitem "
           "GROUP BY l_returnflag")
    assert sig(agg) != sig(agg.replace("sum", "max"))  # agg fn


def test_stage_signature_holds_the_compaction():
    """Where a chain compacts, and how far, is baked into its program:
    q14's chain signs differently for every k, and for none."""
    import dataclasses

    from presto_tpu.planner.plan import AggregationNode

    runner, _ = _fresh_runner()
    ex = runner.executor
    node = runner.binder.plan(QUERIES[14])
    while not isinstance(node, AggregationNode):
        node = node.sources[0]
    root = dataclasses.replace(node, step="partial")
    ex._agg_overrides[root] = ex._max_groups(node)
    chains = {k: ex._lower(root, compact_k=k) for k in (0, 4, 5)}
    sigs = {k: ir_signature(c.signature()) for k, c in chains.items()}
    assert len(set(sigs.values())) == 3
    # the plan's own k
    assert ir_signature(ex._lower(root).signature()) == sigs[5]
    assert chains[0].name() == "chain_leaf_filter_probe_agg_k0a2"
    assert chains[4].name() == chains[5].name() == \
        "chain_leaf_filter_compact_probe_agg_k0a2"


def test_registry_lru_eviction_bounds_callables():
    """The registry must bound the live-executable arena (XLA:CPU
    segfaults past a few thousand live programs — r5 TPC-DS finding):
    oldest callables evict, recent ones survive."""
    reg = ProgramRegistry(max_callables=4)
    for i in range(10):
        reg.get("k", ("sig", i), lambda: (lambda x: x), jit=False)
    assert reg.callable_count() == 4
    assert reg.evictions == 6
    # the most recent signature is still a hit
    misses = reg.misses
    reg.get("k", ("sig", 9), lambda: (lambda x: x), jit=False)
    assert reg.misses == misses and reg.hits == 1


def _child_cache_dir(placed):
    """``jax_compilation_cache_dir`` in a fresh process after the
    first QueryRunner exists, with JAX_COMPILATION_CACHE_DIR=placed
    (None: unset)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if placed is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = placed
    proc = subprocess.run(
        [sys.executable, "-c",
         "import jax, presto_tpu\n"
         "from presto_tpu.catalog import Catalog\n"
         "from presto_tpu.runner import QueryRunner\n"
         "QueryRunner(Catalog())\n"
         "print('DIR=' + str(jax.config.jax_compilation_cache_dir))"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-500:]
    return [ln[4:] for ln in proc.stdout.splitlines()
            if ln.startswith("DIR=")][-1], root


def test_cache_dir_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: no code path moves the cache."""
    placed = str(tmp_path / "placed")
    got, _ = _child_cache_dir(placed)
    assert got == placed


def test_cache_dir_defaults_to_checkout():
    """Unset: the fixed <checkout>/.jax_cache, not a path built from a
    tmpdir, a pid or a data root."""
    import os

    got, root = _child_cache_dir(None)
    assert got == os.path.join(root, ".jax_cache")
