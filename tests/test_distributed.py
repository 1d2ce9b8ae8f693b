"""Distributed execution on the virtual 8-device CPU mesh.

Reference analog: ``DistributedQueryRunner`` tests
(presto-tests/.../DistributedQueryRunner.java:69 — coordinator + N
workers in one JVM); here one process + 8 XLA host devices, comparing
distributed results against the single-device LocalRunner."""

import numpy as np
import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.parallel.dist import DistributedRunner, make_mesh
from presto_tpu.runner import QueryRunner

from tests.tpch_queries import QUERIES


@pytest.fixture(scope="module")
def env():
    tpch = Tpch(sf=0.01, split_rows=4096)
    catalog = Catalog()
    catalog.register("tpch", tpch)
    local = QueryRunner(catalog)
    dist = DistributedRunner(catalog, make_mesh(8))
    return local, dist


def _key(row):
    return tuple(round(v, 6) if isinstance(v, float) else v for v in row)


def _check(local, dist, sql):
    plan = local.plan(sql)
    expected = local.executor.run(plan).rows
    plan2 = local.plan(sql)
    actual = dist.run(plan2).rows
    assert len(actual) == len(expected)
    # exact on ints/strings; 1-ulp tolerance on floats (XLA may fuse
    # the finalize division differently inside shard_map)
    for a, e in zip(sorted(actual, key=_key), sorted(expected, key=_key)):
        for va, ve in zip(a, e):
            if isinstance(va, float):
                assert va == pytest.approx(ve, rel=1e-12), f"{a} != {e}"
            else:
                assert va == ve, f"{a} != {e}"


def test_distributed_q6_global_agg(env):
    local, dist = env
    _check(local, dist, QUERIES[6])


def test_distributed_q1_grouped(env):
    local, dist = env
    _check(local, dist, QUERIES[1])


def test_distributed_q14_join(env):
    local, dist = env
    _check(local, dist, QUERIES[14])


def test_distributed_q3_join_agg_topn(env):
    local, dist = env
    _check(local, dist, QUERIES[3])


def test_distributed_fallback(env):
    """Plans the distributed runner can't shard fall back to local."""
    local, dist = env
    sql = "select count(*) from (select o_orderkey from orders limit 5)"
    _check(local, dist, sql)


# ---------------------------------------------------------------------------
# repartitioned (FIXED_HASH) joins: build sides sharded across devices
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def env_partitioned():
    tpch = Tpch(sf=0.01, split_rows=4096)
    catalog = Catalog()
    catalog.register("tpch", tpch)
    local = QueryRunner(catalog)
    # threshold 0: every join takes the partitioned-exchange path
    dist = DistributedRunner(catalog, make_mesh(8), broadcast_threshold=0)
    return local, dist


def test_partitioned_join_q3(env_partitioned):
    local, dist = env_partitioned
    _check(local, dist, QUERIES[3])


def test_partitioned_join_q9_multijoin(env_partitioned):
    """Q9: five joins (part, supplier, lineitem, partsupp, orders,
    nation) with sharded builds — the large-x-large shape the broadcast
    tier can't scale to."""
    local, dist = env_partitioned
    _check(local, dist, QUERIES[9])


def test_partitioned_join_capacity_retry(env_partitioned):
    """Undersized exchange buckets / expand capacities are detected by
    the in-program counters and retried, never silently truncated."""
    from presto_tpu.planner.plan import JoinNode

    local, dist = env_partitioned
    sql = QUERIES[3]
    plan = local.plan(sql)

    joins = []

    def walk(n):
        if isinstance(n, JoinNode):
            joins.append(n)
        for s in n.sources:
            walk(s)

    walk(plan)
    assert joins
    for j in joins:  # deliberately far too small
        dist._join_cfg[j] = {"bucket_cap": 16, "out_cap": 32, "build_bucket_cap": 16}
    _check(local, dist, sql)
    grew = any(
        dist._join_cfg[j]["bucket_cap"] > 16
        or dist._join_cfg[j]["out_cap"] > 32
        or dist._join_cfg[j]["build_bucket_cap"] > 16
        for j in joins
    )
    assert grew  # the retry protocol actually engaged


def test_fragmenter_join_distribution():
    """The fragmenter chooses broadcast for small builds, repartition
    for large ones (DetermineJoinDistributionType analog)."""
    from presto_tpu.parallel.fragment import (
        decide_join_distribution,
        explain_distributed,
        fragment_plan,
    )
    from presto_tpu.planner.plan import JoinNode

    tpch = Tpch(sf=0.01, split_rows=4096)
    catalog = Catalog()
    catalog.register("tpch", tpch)
    runner = QueryRunner(catalog)
    plan = runner.plan(QUERIES[3])

    joins = []

    def walk(n):
        if isinstance(n, JoinNode):
            joins.append(n)
        for s in n.sources:
            walk(s)

    walk(plan)
    assert joins
    for j in joins:
        mode, est = decide_join_distribution(j, broadcast_threshold=1 << 16)
        assert mode == "broadcast"  # sf0.01 builds are tiny
        mode0, _ = decide_join_distribution(j, broadcast_threshold=0)
        assert mode0 == "partitioned"

    frags = fragment_plan(plan, broadcast_threshold=0)
    txt = frags.tree_str()
    assert "FIXED_HASH" in txt and "SOURCE" in txt and "SINGLE" in txt
    assert explain_distributed(plan).count("Fragment") >= 3


def test_distributed_chain_without_aggregation():
    """Non-aggregate plans distribute too: the streaming chain
    wave-executes on the mesh; sort/limit tails run locally on the
    gathered output (SOURCE-fragment execution of plain queries)."""
    from presto_tpu.catalog import Catalog
    from presto_tpu.connectors.tpch import Tpch
    from presto_tpu.parallel.dist import DistributedRunner, make_mesh
    from presto_tpu.runner import QueryRunner

    cat = Catalog()
    cat.register("tpch", Tpch(sf=0.005, split_rows=1 << 10))
    r = QueryRunner(cat)
    dist = DistributedRunner(cat, make_mesh(8))
    for sql in [
        # filter + sort + limit
        "SELECT l_orderkey, l_quantity FROM lineitem WHERE l_quantity > 45 "
        "ORDER BY l_orderkey, l_quantity, l_extendedprice LIMIT 25",
        # streaming join chain, no aggregation
        "SELECT o_orderkey, c_name FROM orders, customer "
        "WHERE o_custkey = c_custkey AND o_totalprice > 100000.0 "
        "ORDER BY o_orderkey LIMIT 30",
        # bare projection chain
        "SELECT l_orderkey + 1 AS k FROM lineitem WHERE l_linenumber = 7 "
        "ORDER BY k LIMIT 15",
    ]:
        local = r.execute(sql).rows
        assert local, sql  # the fixture must produce rows
        got = dist._run_distributed(r.plan(sql)).rows
        assert got == local, sql


# ---------------------------------------------------------------------------
# generalized stage-DAG decomposition (round 4): arbitrary plan shapes
# lower into multiple mesh stages with materialized intermediates
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def env_general(env):
    local, dist = env
    dist.min_stage_rows = 0  # tiny test pages must still shard
    yield local, dist
    dist.min_stage_rows = 1 << 13


def _check_stages(local, dist, sql, min_stages):
    plan = local.plan(sql)
    got = dist._run_distributed(plan)
    assert dist.last_stage_count >= min_stages, (
        sql[:60], dist.last_stage_count)
    want = local.executor.run(local.plan(sql))
    assert len(got.rows) == len(want.rows)
    for a, e in zip(sorted(got.rows, key=_key), sorted(want.rows, key=_key)):
        for va, ve in zip(a, e):
            if isinstance(va, float):
                assert va == pytest.approx(ve, rel=1e-12), (a, e)
            else:
                assert va == ve, (a, e)


def test_multi_level_aggregation_distributes(env_general):
    """Aggregation over a subquery aggregation: both levels are mesh
    stages — the inner agg's merged output re-chunks across devices as
    the outer stage's source (multi-fragment SubPlan execution)."""
    local, dist = env_general
    _check_stages(
        local, dist,
        "SELECT max(c) AS mx, min(ok) AS mn, count(*) AS n FROM "
        "(SELECT o_custkey AS ok, count(*) AS c FROM orders GROUP BY o_custkey)",
        min_stages=2,
    )


def test_union_arms_distribute(env_general):
    """Each UNION ALL arm wave-executes as its own stage; the
    coordinator concatenates; an aggregation above shards again."""
    local, dist = env_general
    _check_stages(
        local, dist,
        "SELECT count(*) AS n, sum(k) AS s FROM ("
        "SELECT o_orderkey AS k FROM orders WHERE o_orderkey % 2 = 0 "
        "UNION ALL "
        "SELECT l_orderkey AS k FROM lineitem WHERE l_linenumber = 1)",
        min_stages=3,
    )


def test_window_glue_between_stages(env_general):
    """A window function between two aggregations: stage below, window
    on the coordinator (glue), stage above over its output."""
    local, dist = env_general
    _check_stages(
        local, dist,
        "SELECT count(*) AS n, max(rnk) AS top FROM ("
        "  SELECT o_custkey, rank() OVER (ORDER BY c DESC) AS rnk FROM ("
        "    SELECT o_custkey, count(*) AS c FROM orders GROUP BY o_custkey))"
        " WHERE rnk <= 10",
        min_stages=1,
    )


def test_tpcds_q7_distributes(env_general):
    """A real TPC-DS star-join query through the general decomposition,
    validated against LocalRunner (VERDICT r3 next-round item 2)."""
    from presto_tpu.catalog import Catalog
    from presto_tpu.connectors.tpcds import Tpcds
    from presto_tpu.parallel.dist import DistributedRunner, make_mesh
    from tests.tpcds_queries import QUERIES as DS

    cat = Catalog()
    cat.register("tpcds", Tpcds(sf=0.002, split_rows=512,
                                cd_rows=2 * 5 * 7 * 4, inv_rows=2000))
    local = QueryRunner(cat)
    dist = DistributedRunner(cat, make_mesh(8))
    dist.min_stage_rows = 0
    _check_stages(local, dist, DS[7], min_stages=1)


def test_fallback_is_loud(env):
    """An undistributable plan must fall back with a recorded reason
    (VERDICT r3: the silent LocalRunner fallback hid that no TPC-DS
    query distributed)."""
    local, dist = env
    # VALUES-only plan: no scan, nothing to shard
    plan = local.plan("SELECT * FROM (VALUES (1, 'a'), (2, 'b')) t(x, y)")
    res = dist.run(plan)
    assert len(res.rows) == 2
    assert dist.last_stage_count == 0
    assert dist.last_fallback_reason  # non-empty, human-readable


def test_explain_fragmented_header(env):
    """EXPLAIN (TYPE DISTRIBUTED) leads with the loud FRAGMENTED header
    that always agrees with what execution does."""
    from presto_tpu.parallel.fragment import explain_distributed

    local, _ = env
    yes = explain_distributed(local.plan(QUERIES[3]))
    assert yes.startswith("FRAGMENTED: yes")
    no = explain_distributed(
        local.plan("SELECT * FROM (VALUES (1), (2)) t(x)"))
    assert no.startswith("FRAGMENTED: no")
    assert "coordinator" in no


def test_completed_event_carries_dist_outcome(env):
    """Query events surface distributed-vs-local per query."""
    from presto_tpu.catalog import Catalog
    from presto_tpu.connectors.tpch import Tpch
    from presto_tpu.events import EventListener

    cat = Catalog()
    cat.register("tpch", Tpch(sf=0.002, split_rows=512))
    r = QueryRunner(cat)
    r.session.set("distributed", "true")
    seen = []

    class L(EventListener):
        def query_completed(self, event):
            seen.append(event)

    r.events.add(L())
    r.execute("SELECT count(*) FROM orders")
    assert seen and seen[-1].dist_stages >= 1
    assert seen[-1].dist_fallback is None


def test_per_shard_topn_bound(env):
    """A TopN/Limit consumer bounds each shard's gather to its count
    (CreatePartialTopN.java role): the fragment advertises shard_bound
    and distributed results match local exactly."""
    runner, dist = env
    sql = ("select l_orderkey, l_extendedprice from lineitem "
           "where l_quantity > 10 order by l_extendedprice desc, "
           "l_orderkey limit 5")
    plan = runner.plan(sql)
    got = dist.run(plan)
    assert dist.last_fallback_reason is None
    want = runner.execute(sql)
    assert [tuple(map(float, r)) for r in got.rows] \
        == [tuple(map(float, r)) for r in want.rows]
    from presto_tpu.parallel.fragment import fragment_plan

    frag = fragment_plan(runner.plan(sql))
    bounds = []

    def walk(f):
        bounds.append(f.shard_bound)
        for c in f.children:
            walk(c)

    walk(frag)
    assert 5 in bounds


def test_per_shard_limit_bound(env):
    runner, dist = env
    sql = "select l_orderkey from lineitem where l_quantity > 30 limit 7"
    got = dist.run(runner.plan(sql))
    assert dist.last_fallback_reason is None
    assert len(got.rows) == 7
    # every returned row satisfies the predicate (local check)
    keys = {r[0] for r in runner.execute(
        "select l_orderkey from lineitem where l_quantity > 30").rows}
    assert all(r[0] in keys for r in got.rows)


# ---------------------------------------------------------------------------
# device-resident tables: the connector every benchmark run uses
# ---------------------------------------------------------------------------

def test_distributed_over_resident_tables_with_ragged_last_split():
    """Load-time ladder padding leaves a MemoryConnector's last split
    at a smaller capacity than its siblings; the mesh tier stacks one
    split per device into a wave page, so every split must come back at
    the one capacity it asks for (this crashed in ``_stack_pages`` with
    "all input arrays must have the same shape")."""
    from presto_tpu.connectors.memory import MemoryConnector

    tpch = Tpch(sf=0.01, split_rows=4096)
    mem = MemoryConnector()
    for table in ("lineitem", "orders", "customer"):
        mem.load_from(tpch, table)
    caps = {mem.page_for_split("lineitem", s).capacity
            for s in range(mem.num_splits("lineitem"))}
    assert len(caps) > 1, "fixture lost its ragged split"
    catalog = Catalog()
    catalog.register("mem", mem)
    runner = QueryRunner(catalog)
    for qid in (6, 3):
        expected = runner.execute(QUERIES[qid]).rows
        runner.execute("SET SESSION distributed = true")
        res = runner.execute(QUERIES[qid])
        runner.execute("SET SESSION distributed = false")
        assert res.dist_fallback is None and res.dist_stages >= 1, (
            qid, res.dist_fallback, res.dist_stages)
        assert res.rows == expected
