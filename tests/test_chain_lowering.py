"""One description of a streaming chain (exec/chain.py) and one cache
for its program (exec/programs.py): a stage's every parameter is in
the chain's signature; what a run registers is what ``lower_chain``
describes; a served runner keeps nothing per statement; a capacity
retry and a demoted build find their programs by signature alone; the
probes of one chain open ``probe:<i>`` inside ``op:Join``, which is
metadata and no part of a program's text, and a statement's final page
counts them (``stats.chainProbes``)."""

import dataclasses
import re

import jax

import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.exec import chain as chain_mod
from presto_tpu.exec.chain import KINDS, Chain, Stage, chain_leaf
from presto_tpu.exec.local import LocalRunner
from presto_tpu.exec.programs import ProgramRegistry, ir_signature
from presto_tpu.expr.ir import ColumnRef, Expr
from presto_tpu.memory import MemoryPool
from presto_tpu.planner.plan import AggregationNode, JoinNode
from presto_tpu.runner import QueryRunner
from presto_tpu.sql.binder import Binder
from presto_tpu.types import BIGINT

from tests.oracle import assert_rows_match
from tests.tpcds_queries import QUERIES as DS_QUERIES
from tests.tpch_queries import QUERIES


@pytest.fixture(scope="module")
def catalog():
    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=0.01))
    return catalog


def _fresh(catalog):
    registry = ProgramRegistry()
    return QueryRunner(catalog, programs=registry), registry


# -- (a) every field of every stage kind is in the signature ----------------

def _other(value):
    """A value of the same sort as ``value`` and not equal to it."""
    if isinstance(value, bool):
        return not value
    if isinstance(value, int):
        return value + 1
    if isinstance(value, str):
        return value + "_"
    if isinstance(value, tuple):
        return value + ((value[0],) if value else ((0, 1),))
    assert isinstance(value, Expr), value
    return ColumnRef(type=BIGINT, index=1 << 20)


@pytest.fixture(scope="module")
def stages(catalog):
    """One real stage of each kind that a plan node gives, by kind:
    q14's chain (filter, compact, probe, partial aggregation) with the
    projection over its answer, and q3's (lookup, compact, fetch)."""
    runner, _ = _fresh(catalog)
    ex = runner.executor
    found = {}
    for q in (14, 3):
        node = runner.binder.plan(QUERIES[q])
        while node.sources:
            if isinstance(node, AggregationNode):
                node = dataclasses.replace(node, step="partial")
            for stage in ex._lower(node).stages:
                found.setdefault(stage.kind, stage)
            node = node.sources[0]
    return found


FIELDS = [(kind, field) for kind, params in sorted(KINDS.items())
          for field in params._fields]


def test_stage_kinds_are_the_grammar():
    assert sorted(KINDS) == ["agg_partial", "compact", "cross1", "fetch",
                             "filter", "lookup", "probe", "project"]
    assert len(FIELDS) >= 16


@pytest.mark.parametrize("kind,field", FIELDS)
def test_every_param_is_in_the_signature(stages, kind, field):
    """Changing one field of one stage's params, and nothing else,
    changes the chain's signature: ``apply`` sees the params only, so
    nothing a program bakes in is missing from its key."""
    base = stages[kind].params
    assert type(base) is KINDS[kind]
    changed = base._replace(**{field: _other(getattr(base, field))})

    def sig(params):
        chain = Chain(None, (stages["filter"], Stage(kind, params)))
        return ir_signature(chain.signature())

    assert sig(base) == sig(KINDS[kind](*base))
    assert sig(base) != sig(changed)
    # and of a prefix that holds the stage, not of one that ends before
    chain = Chain(None, (Stage(kind, changed), stages["filter"]))
    other = Chain(None, (Stage(kind, base), stages["filter"]))
    assert ir_signature(chain.signature(1)) != ir_signature(other.signature(1))
    assert ir_signature(chain.signature(0)) == ir_signature(other.signature(0))


# -- (b) what a run registers is what lower_chain describes -----------------

# every chain program each query registers at SF0.01, by name
CHAINS = {
    1: {"chain_leaf_filter_agg_k2a8", "chain_leaf_project"},
    3: {"chain_leaf_filter", "chain_leaf_filter_probe",
        "chain_leaf_filter_probe_agg_k3a1_compact_in_probe0",
        "chain_leaf_project"},
    4: {"chain_leaf_filter", "chain_leaf_filter_compact_probe_agg_k1a1",
        "chain_leaf_project"},
    6: {"chain_leaf_filter_agg_k0a1", "chain_leaf_project"},
    12: {"chain_leaf", "chain_leaf_filter_compact_probe_agg_k1a2",
         "chain_leaf_project"},
    13: {"chain_leaf", "chain_leaf_agg_k1a1", "chain_leaf_filter",
         "chain_leaf_project", "chain_leaf_project_agg_k1a1"},
    14: {"chain_leaf", "chain_leaf_filter_compact_probe_agg_k0a2",
         "chain_leaf_project"},
}


@pytest.mark.parametrize("q", sorted(CHAINS))
def test_the_registry_holds_what_lower_chain_describes(catalog, q,
                                                       monkeypatch):
    runner, registry = _fresh(catalog)
    ex = runner.executor
    lowered = []
    lower = ex._lower

    def recording(node, compact_k=None):
        lowered.append((node, lower(node, compact_k)))
        return lowered[-1][1]

    monkeypatch.setattr(ex, "_lower", recording)
    assert runner.execute(QUERIES[q]).rows
    held = {key: prog for key, prog in registry._programs.items()
            if key[0] == "chain"}
    assert {prog.fn.__name__ for prog in held.values()} == CHAINS[q]
    assert lowered
    for node, chain in lowered:
        key = ("chain", True, ir_signature(chain.signature()))
        assert held[key].fn.__name__ == chain.name()
        assert chain.leaf is chain_leaf(node, ex._streaming)
        assert not chain_mod._member(chain.leaf, ex._streaming)
        tags = chain.name().split("_")
        assert len(chain.joins) == tags.count("probe") + tags.count("cross1")
        kinds = [s.kind for s in chain.stages]
        assert kinds.count("lookup") == kinds.count("fetch") == \
            chain.name().count("_compact_in_probe")
        assert all(isinstance(j, JoinNode) for j in chain.joins)
        assert [s.node for s in chain.stages
                if s.kind not in ("compact", "lookup")] == \
            _members(node, chain.leaf)
    # every registered chain was described by a lowering of this run
    assert set(held) == {("chain", True, ir_signature(c.signature()))
                         for _, c in lowered}


def _members(root, leaf):
    """The chain's member nodes, leaf first, by the plan's own links."""
    out, node = [], root
    while node is not leaf:
        out.append(node)
        node = node.sources[0]
    return out[::-1]


# -- (c) a served runner keeps nothing per statement ------------------------

def _sizes(ex):
    return {name: len(value) for name, value in vars(ex).items()
            if isinstance(value, (dict, set, list))}


def test_twenty_new_texts_add_no_program_and_no_entry(catalog):
    runner, registry = _fresh(catalog)
    ex = runner.executor
    rows, callables, sizes = [], [], []
    for i in range(20):
        rows.append(runner.execute(QUERIES[3] + " " * i).rows)
        callables.append(registry.callable_count())
        sizes.append(_sizes(ex))
    assert all(r == rows[0] for r in rows)
    assert callables[19] == callables[0]
    assert registry.program_count() == 8  # q3's, as in PROGRAM_COUNTS
    # D6/D11: these two still grow by a plan node a statement
    grown = {name for name in sizes[19]
             if sizes[19][name] > sizes[1].get(name, 0)}
    assert grown <= {"_agg_overrides", "_partial_nodes"}, grown


# -- (d) retries and demotions find their programs by signature -------------

AGG_SQL = ("select l_orderkey, count(*), sum(l_quantity), max(l_extendedprice)"
           " from lineitem group by l_orderkey")
JOIN_SQL = ("select o_orderkey, o_totalprice, l_quantity from orders, lineitem"
            " where o_orderkey = l_orderkey and l_linenumber = 1")


@pytest.fixture(scope="module")
def small():
    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=0.004, split_rows=1 << 12))
    return catalog


def test_capacity_retry_answers_without_invalidation(small):
    """A partial aggregation that overflows its capacity raises
    ``GroupCapacityExceeded``; the re-run lowers the chain at the new
    capacity, a new signature, and the registry hands out the right
    program by itself.  The same plan object again: nothing stale."""
    expected = LocalRunner(small).run(Binder(small).plan(AGG_SQL)).rows
    plan = Binder(small).plan(AGG_SQL)
    agg = plan
    while not isinstance(agg, AggregationNode):
        agg = agg.source
    agg.max_groups = 1 << 10  # 6,000 orders: overflows
    registry = ProgramRegistry()
    runner = LocalRunner(small, programs=registry)
    assert_rows_match(runner.run(plan).rows, expected, ordered=False)
    assert runner._agg_overrides[agg] > 1 << 10  # it did retry
    chains = [key for key in registry._programs if key[0] == "chain"
              and "agg_partial" in repr(key)]
    assert len(chains) == 2  # the capacity is in the signature
    misses = registry.misses
    assert_rows_match(runner.run(plan).rows, expected, ordered=False)
    assert registry.misses == misses


def test_demoted_build_answers_without_invalidation(small):
    """A build that does not fit the pool demotes its join out of the
    chain (``_force_expanding``): ``_streaming`` then says no, so the
    chain is lowered without the probe and nothing has to be told."""
    plan = Binder(small).plan(JOIN_SQL)
    expected = LocalRunner(small).run(plan).rows

    seen = {}

    class Peek(MemoryPool):
        def reserve(self, tag, nbytes, enforce=True):
            seen[tag] = nbytes
            super().reserve(tag, nbytes, enforce=enforce)

    LocalRunner(small, memory_pool=Peek(1 << 40)).run(plan)
    build_bytes = max(n for t, n in seen.items() if "join_build@" in t)
    runner = LocalRunner(small, memory_pool=MemoryPool(int(build_bytes * 0.6)))
    assert any(s.kind == "probe" for s in _probe_chain(runner, plan).stages)
    assert_rows_match(runner.run(plan).rows, expected, ordered=False)
    assert len(runner._force_expanding) == 1
    assert not any(s.kind == "probe"
                   for s in _probe_chain(runner, plan).stages)
    assert_rows_match(runner.run(plan).rows, expected, ordered=False)


def _probe_chain(runner, plan):
    """The chain rooted at the plan's first join, as ``runner`` lowers
    it now."""
    node = plan
    while not isinstance(node, JoinNode):
        node = node.sources[0]
    return runner._lower(node)


# -- (e) the probes of one chain are told apart -----------------------------

@pytest.fixture(scope="module")
def star():
    """TPC-DS's ``store_sales`` star at SF0.01, the demographic cross
    product cut to 20,000 rows."""
    from presto_tpu.connectors.tpcds import Tpcds

    catalog = Catalog()
    catalog.register("tpcds", Tpcds(sf=0.01, cd_rows=20000))
    return catalog


def _probing_chains(catalog, sql):
    """(chain, one of its pages, its consts) of every chain of ``sql``
    that probes, taken where the executor calls the chain's program."""
    runner, _ = _fresh(catalog)
    ex = runner.executor
    program, called = ex._chain_program, []

    def recording(chain):
        fn = program(chain)

        def call(page, consts):
            called.append((chain, page, consts))
            return fn(page, consts)

        return call

    ex._chain_program = recording
    assert runner.execute(sql).rows
    probing = {id(c[0]): c for c in reversed(called) if c[0].probes}
    assert probing
    return list(probing.values())


def _debug_text(chain, page, consts, upto=None):
    return jax.jit(chain.fn(upto)).lower(page, consts).as_text(
        debug_info=True)


def _scopes(chain, page, consts, upto=None):
    """(the ``<outer>/probe:<i>`` scope pairs, the count of ``probe:``
    scopes) in the lowered chain's debug locations."""
    text = _debug_text(chain, page, consts, upto)
    return (set(re.findall(r"([a-z]+:[A-Za-z]+)/(probe:\d+)", text)),
            len(re.findall(r"/probe:\d+", text)))


@pytest.mark.parametrize("q,k", [(3, 2), (7, 4)])
def test_a_chain_of_k_probes_opens_k_scopes_inside_op_join(star, q, k):
    (chain, page, consts), = _probing_chains(star, DS_QUERIES[q])
    assert chain.probes == k == len(chain.joins)
    assert chain.name().count("_probe") - 1 == k  # ..._compact_in_probe0
    pairs, n = _scopes(chain, page, consts)
    assert pairs == {("op:Join", f"probe:{i}") for i in range(k)}
    # nowhere but directly inside op:Join
    text = _debug_text(chain, page, consts)
    assert n == len(re.findall(r"op:Join/probe:\d+", text)) > 0
    # the unfiltered fact chain compacts inside its first probe: the
    # lookup and the fetch share probe:0, the compaction between them
    # is the filter's
    kinds = [s.kind for s in chain.stages]
    at = kinds.index("lookup")
    assert kinds[at:at + 3] == ["lookup", "compact", "fetch"]
    assert kinds.count("probe") == k - 1 and "probe" not in kinds[:at]
    for upto in (at + 1, at + 2, at + 3):
        assert _scopes(chain, page, consts, upto)[0] == {
            ("op:Join", "probe:0")}
    lookup_only = _scopes(chain, page, consts, at + 1)[1]
    assert _scopes(chain, page, consts, at + 2)[1] == lookup_only
    assert _scopes(chain, page, consts, at + 3)[1] > lookup_only
    if k > 1:
        assert _scopes(chain, page, consts, at + 4)[0] == {
            ("op:Join", "probe:0"), ("op:Join", "probe:1")}


@pytest.mark.parametrize("q", [14, 3])
def test_the_probe_scope_is_no_part_of_a_programs_text(catalog, q,
                                                       monkeypatch):
    """q14's and q3's chains lower to the text they had before the
    scope (what the persistent compile cache hashes, so no cell's
    programs compile anew); only the debug locations differ."""
    chains = _probing_chains(catalog, QUERIES[q])
    # q3 probes in two chains: orders against customer, lineitem
    # against that join
    assert len(chains) == {14: 1, 3: 2}[q]
    lowered = [jax.jit(chain.fn()).lower(page, consts)
               for chain, page, consts in chains]
    for low in lowered:
        assert "probe:" not in low.as_text()
        assert "op:Join/probe:0" in low.as_text(debug_info=True)
    named_scope = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: named_scope(name.split("/probe:")[0]))
    for (chain, page, consts), low in zip(chains, lowered):
        before = jax.jit(chain.fn()).lower(page, consts)
        assert "probe:" not in before.as_text(debug_info=True)
        assert before.as_text() == low.as_text()


def test_chain_probes_ride_the_final_page(star):
    """``stats.chainProbes`` beside ``arithChecked``: 4 for ds_q07's
    fact chain, 0 for a statement whose chains probe nothing."""
    from presto_tpu.client import StatementClient
    from presto_tpu.server.coordinator import CoordinatorServer

    srv = CoordinatorServer(QueryRunner(star))
    srv.start()
    try:
        client = StatementClient(srv.uri)
        counts = {}
        for name, sql in (("ds_q07", DS_QUERIES[7]), ("ds_q03", DS_QUERIES[3]),
                          ("scan", "select sum(ss_quantity) from store_sales "
                                   "where ss_quantity < 24")):
            pages = []
            client.execute(sql, on_progress=pages.append)
            assert "arithChecked" in pages[-1]
            counts[name] = pages[-1]["chainProbes"]
    finally:
        srv.stop()
    assert counts == {"ds_q07": 4, "ds_q03": 2, "scan": 0}
