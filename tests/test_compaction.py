"""Selection compaction in front of a probe or between its lookup and
its fetch (ops/filter_project ``compact_page``, exec/chain
``lower_chain``'s ``compact`` stage, exec/local ``_chain_pages``): the
primitive keeps order, validity and count; a chain that compacts
answers as the chain that does not, whether its pages fit or the
aggregation over it has to start again; chains the gate leaves alone
build the program they always built."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.exec import chain as chain_mod
from presto_tpu.exec import local
from presto_tpu.ops.filter_project import compact_page
from presto_tpu.page import Block, Dictionary, Page
from presto_tpu.planner.plan import AggregationNode, JoinNode, LimitNode
from presto_tpu.runner import QueryRunner
from presto_tpu.types import BIGINT, DATE, VARCHAR

from tests.oracle import assert_rows_match
from tests.pandas_oracle import PANDAS_QUERIES, load_frames
from tests.tpch_queries import QUERIES

CAP, CAP_OUT = 64, 8
# live rows of the 64-slot page, by name of the case
LIVE = {
    "none": [],
    "one": [41],
    "exactly_cap_out": [1, 2, 3, 17, 30, 31, 62, 63],
    "cap_out_plus_one": [0, 5, 6, 7, 20, 33, 40, 50, 63],
    "all": list(range(CAP)),
}


def _block(kind):
    values = np.arange(CAP, dtype=np.int64) * 7 + 3
    if kind == "bigint":
        return Block.from_numpy(values, BIGINT)
    if kind == "date":
        return Block.from_numpy(values.astype(np.int32), DATE)
    if kind == "dictionary_varchar":
        d = Dictionary([f"v{i}" for i in range(5)])
        return Block.from_numpy((values % 5).astype(np.int32), VARCHAR,
                                dictionary=d)
    assert kind == "nullable"
    return Block.from_numpy(values, BIGINT, valid=(values % 3 != 0))


@pytest.mark.parametrize("kind", ["bigint", "date", "dictionary_varchar",
                                  "nullable"])
@pytest.mark.parametrize("case", sorted(LIVE))
def test_compact_page(case, kind):
    block = _block(kind)
    mask = np.zeros(CAP, dtype=np.bool_)
    mask[LIVE[case]] = True
    small, live = jax.jit(compact_page, static_argnums=1)(
        Page((block,), jnp.asarray(mask)), CAP_OUT)
    assert int(live) == len(LIVE[case])  # the count is of the whole page
    kept = LIVE[case][:CAP_OUT]
    assert small.capacity == CAP_OUT
    assert np.asarray(small.row_mask).tolist() == (
        [True] * len(kept) + [False] * (CAP_OUT - len(kept)))
    out = small.blocks[0]
    assert out.type == block.type and out.dictionary is block.dictionary
    assert out.data.dtype == block.data.dtype
    # the live rows, in their order, first; a dead slot is not valid
    assert np.array_equal(np.asarray(out.data)[:len(kept)],
                          np.asarray(block.data)[kept])
    assert np.asarray(out.valid).tolist() == (
        np.asarray(block.valid)[kept].tolist()
        + [False] * (CAP_OUT - len(kept)))


# -- the chain ---------------------------------------------------------------

@pytest.fixture(scope="module")
def env():
    tpch = Tpch(sf=0.01, split_rows=16384)
    catalog = Catalog()
    catalog.register("tpch", tpch)
    return QueryRunner(catalog), load_frames(tpch)


def _chain_root(runner, sql):
    """The partial aggregation over the streaming chain of ``sql``'s
    plan, as the executor injects it."""
    ex = runner.executor
    node = runner.binder.plan(sql)
    while not isinstance(node, AggregationNode):
        node = node.sources[0]
    import dataclasses

    root = dataclasses.replace(node, step="partial")
    ex._agg_overrides[root] = ex._max_groups(node)
    return root


def _force_k(monkeypatch, ex, k):
    """Every chain ``ex`` lowers from here on, where it compacts,
    compacts at ``k``."""
    lower = ex._lower
    monkeypatch.setattr(
        ex, "_lower", lambda node, compact_k=None: lower(
            node, k if compact_k is None else compact_k))


def _counts(runner, sql):
    before = local.compact_counts()
    rows = runner.execute(sql).rows
    return rows, tuple(n - n0 for n, n0 in
                       zip(local.compact_counts(), before))


def test_q14_compacts_and_matches_the_oracle(env):
    runner, frames = env
    rows, (compacted, fallback) = _counts(runner, QUERIES[14])
    assert_rows_match(rows, PANDAS_QUERIES[14](frames), ordered=False)
    assert compacted == 4 and fallback == 0  # four lineitem splits


def test_q14_falls_back_and_matches_the_oracle(env, monkeypatch):
    """k = 12 leaves 16384 >> 12 = 4 slots for about 190 live rows a
    page: the aggregation starts again over the program that does not
    compact, and the answer is the same."""
    runner, frames = env
    _force_k(monkeypatch, runner.executor, 12)
    rows, (compacted, fallback) = _counts(runner, QUERIES[14] + " ")
    assert_rows_match(rows, PANDAS_QUERIES[14](frames), ordered=False)
    assert compacted == 0 and fallback == 4


@pytest.mark.parametrize("q", [1, 6])
def test_other_queries_compact_nothing(env, q):
    runner, frames = env
    rows, counts = _counts(runner, QUERIES[q])
    assert_rows_match(rows, PANDAS_QUERIES[q](frames), ordered=False)
    assert counts == (0, 0)


def _programs(runner, root, k):
    """(today's program, the compacting one at ``k``, their consts,
    the chain's pages) through the lowered chain."""
    ex = runner.executor
    lowered, whole = ex._lower(root, k), ex._lower(root, 0)
    assert lowered.compacts and not whole.compacts
    plain = jax.jit(whole.fn())
    chain = jax.jit(lowered.fn())
    assert whole.joins == lowered.joins
    assert [type(j) for j in lowered.joins] == [JoinNode]
    consts = {"build_0": ex._materialize_build(lowered.joins[0])}
    pages = list(ex._source_pages(lowered.leaf))
    assert len(pages) == 4
    return plain, chain, consts, pages


def _compaction(lowered):
    """(kind of the stage the chain compacts in front of, k), or
    None; the kind is ``fetch`` where it compacts inside a probe."""
    for i, stage in enumerate(lowered.stages):
        if stage.kind == "compact":
            return lowered.stages[i + 1].kind, stage.params.k
    return None


def _same_page(got, want):
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_compacted_partial_aggregate_equals_the_uncompacted_programs(env):
    """Page by page, through the stage builders: where the live rows
    fit, the compacting program's partial aggregate is today's."""
    runner, _ = env
    root = _chain_root(runner, QUERIES[14])
    # 2.4% fits 1/32
    assert _compaction(runner.executor._lower(root)) == ("probe", 5)
    plain, chain, consts, pages = _programs(runner, root, 5)
    for page in pages:
        got, over = chain(page, consts)
        assert not bool(over)
        _same_page(got, plain(page, consts))


def test_a_page_that_does_not_fit_says_so_and_the_chain_runs_whole(
        env, monkeypatch):
    """k = 12 leaves 4 slots for about 190 live rows: every page of
    the compacting program reports the miss.  ``_chain_pages`` hands
    each page on as it comes, holds none, and raises after the last;
    the chain is marked, and then gives the partial aggregates of
    today's program, page for page.  Without a consumer that can
    start again it never compacts at all."""
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[14])
    plain, chain, consts, pages = _programs(runner, root, 12)
    for page in pages:
        assert bool(chain(page, consts)[1])
    _force_k(monkeypatch, ex, 12)
    before = local.compact_counts()
    for got, page in zip(ex._chain_pages(root), pages, strict=True):
        _same_page(got, plain(page, consts))  # nobody asked: whole
    assert local.compact_counts() == before
    monkeypatch.setattr(ex._restartable_tls, "root", root, raising=False)
    outs = ex._chain_pages(root)
    taken = [next(outs) for _ in pages]  # each as it is produced
    assert root not in ex._no_compact
    with pytest.raises(local.CompactionMissed) as missed:
        next(outs)
    assert missed.value.node is root and root in ex._no_compact
    assert local.compact_counts() == (before[0], before[1] + 4)
    assert len(taken) == len(pages)
    for got, page in zip(ex._chain_pages(root), pages, strict=True):
        _same_page(got, plain(page, consts))
    assert local.compact_counts() == (before[0], before[1] + 4)


def test_a_prefix_of_the_chain_compacts_as_the_chain(env):
    """``_time_chain`` times prefixes that end in the probe: the
    chain's prefix gives the small page there, same live rows."""
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[14])
    probe = root.source
    assert isinstance(probe, JoinNode)
    assert not ex._lower(probe).compacts  # the gate wants the partial
    lowered = ex._lower(root)
    upto = [s.node for s in lowered.stages].index(probe) + 1
    assert lowered.name(upto) == "chain_leaf_filter_compact_probe"
    plain = ex._lower(probe).fn()
    chain = lowered.fn(upto)
    consts = {"build_0": ex._materialize_build(lowered.joins[0])}
    page = next(iter(ex._source_pages(lowered.leaf)))
    want = plain(page, consts)
    got, over = chain(page, consts)
    assert not bool(over)
    assert got.capacity == page.capacity >> 5 and want.capacity == page.capacity
    assert got.to_pylist() == want.to_pylist()  # live rows, in order


# -- inside a probe: lookup, compact, fetch ----------------------------------

Q3_NAME = "chain_leaf_filter_probe_agg_k3a1_compact_in_probe0"


def _live(page):
    """The live rows' values, column by column."""
    mask = np.asarray(page.row_mask)
    return [(np.asarray(b.data)[mask], np.asarray(b.valid)[mask])
            for b in page.blocks]


def test_q3_compacts_between_lookup_and_fetch(env):
    """q3's lineitem filter keeps 54% of the rows and its probe, by
    the planner's estimate, 7.8% of the scan's (really 0.5%): nothing
    in front of the probe, k = 2 inside it, at SF0.01 as at SF1."""
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[3])
    lowered = ex._lower(root)
    assert _compaction(lowered) == ("fetch", 2)
    assert [s.kind for s in lowered.stages] == [
        "filter", "lookup", "compact", "fetch", "agg_partial"]
    # the two halves are one join: one build, booked to the fetch
    probe = root.source
    assert isinstance(probe, JoinNode) and probe.kind == "inner"
    assert lowered.joins == [probe]
    assert [s.node for s in lowered.stages] == [
        probe.left, None, None, probe, root]
    from presto_tpu.planner.stats import StatsCalculator

    calc = StatsCalculator()
    share = chain_mod._estimated_share(probe, lowered.leaf, calc)
    assert 2.0 ** -3 < 2 * share <= 2.0 ** -2
    assert chain_mod._filtered_share(probe.left, calc) > 0.5


@pytest.mark.parametrize("k", [2, 5])
def test_q3_compacted_partial_pages_equal_the_uncompacted(env, k):
    """Group for group: the compacting program's partial page holds
    the groups of today's, in today's order, in a page no larger than
    its small input page (a page has no more groups than rows)."""
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[3])
    assert ex._lower(root, k).name() == Q3_NAME
    assert ex._lower(root, 0).name() == "chain_leaf_filter_probe_agg_k3a1"
    plain, chain, consts, pages = _programs(runner, root, k)
    for page in pages:
        got, over = chain(page, consts)
        want = plain(page, consts)
        assert not bool(over)
        assert got.capacity == min(want.capacity, page.capacity >> k)
        assert int(got.num_rows()) == int(want.num_rows()) > 0
        for (a, av), (b, bv) in zip(_live(got), _live(want)):
            assert np.array_equal(a, b) and np.array_equal(av, bv)


def test_q3_compacts_and_matches_the_oracle(env):
    runner, frames = env
    rows, counts = _counts(runner, QUERIES[3])
    assert_rows_match(rows, PANDAS_QUERIES[3](frames), ordered=False)
    assert counts == (4, 0)  # four lineitem splits, none missed


def test_q3_forced_miss_starts_the_aggregation_again(env, monkeypatch):
    """k = 13 leaves 2 slots for some thirty matched rows a page.  The
    chain raises once, after its last page; the aggregation drops the
    tower it built from those pages, and runs its source again, whole.
    The answer is exact (a page of the first try in it would show:
    every sum is of all of a group's rows or it is wrong), and the
    counter says which pages were thrown away."""
    runner, frames = env
    ex = runner.executor
    _force_k(monkeypatch, ex, 13)
    tries, released = [], []
    fold, release = ex._fold_partials, local._AggFoldTower.release

    def folding(node, source, mg):
        tries.append(source)
        return fold(node, source, mg)

    def releasing(tower):
        released.append(tower.live_total)
        release(tower)
        assert not tower.levels

    monkeypatch.setattr(ex, "_fold_partials", folding)
    monkeypatch.setattr(local._AggFoldTower, "release", releasing)
    rows, counts = _counts(runner, QUERIES[3] + "  ")
    assert_rows_match(rows, PANDAS_QUERIES[3](frames), ordered=False)
    assert counts == (0, 4)
    # q3's one aggregation, twice over the same partial; the first
    # try's tower had taken pages (2 groups each at most) and was dropped
    assert len(tries) == 2 and tries[0] is tries[1]
    assert tries[0] in ex._no_compact
    assert len(released) == 1 and 0 < released[0] <= 2 * 4
    assert not ex._lower(tries[0]).compacts  # marked: whole from now on


def _with_join(root, **changes):
    """``root``'s chain with its one join changed."""
    import dataclasses

    join = root.source
    assert isinstance(join, JoinNode)
    return dataclasses.replace(
        root, source=dataclasses.replace(join, **changes))


@pytest.mark.parametrize("changes", [
    {"kind": "left"}, {"kind": "semi"}, {"kind": "anti"}, {"kind": "mark"},
    {"unique_build": False},
], ids=lambda c: "-".join(f"{k}={v}" for k, v in c.items()))
def test_only_an_inner_probe_of_a_unique_build_compacts_inside(env, changes):
    """``left`` keeps every probe row and the presence tests fetch
    nothing, so there is nothing to save between their halves; a build
    that is not unique does not probe inside a chain at all.  Not even
    where k is forced."""
    runner, _ = env
    ex = runner.executor
    # its aggregation reads probe columns only: any kind can feed it
    root = _chain_root(
        runner, "select sum(l_extendedprice) from lineitem, orders where "
                "l_orderkey = o_orderkey and o_orderdate < date '1992-03-01'")
    assert _compaction(ex._lower(root)) == ("fetch", 4)
    other = _with_join(root, **changes)
    ex._agg_overrides[other] = ex._agg_overrides[root]
    for k in (None, 3):
        lowered = ex._lower(other, k)
        assert not lowered.compacts
        assert not {"lookup", "fetch"} & {s.kind for s in lowered.stages}
    if "unique_build" in changes:
        assert ex._lower(other).leaf is other.source  # a breaker now


@pytest.mark.parametrize("proving", [True, False],
                         ids=["as_the_executor_runs_it", "without_intervals"])
def test_a_filter_in_front_wins_and_the_program_is_the_plain_composition(
        env, proving):
    """q14's chain has both: a filter in front of its probe that
    qualifies, and an inner probe of a unique build.  It compacts in
    front, once, and its program is what it was before a chain could
    compact anywhere else: filter, compact, the whole probe, the
    partial aggregation, to the letter of the lowered text."""
    from presto_tpu.ops.aggregate import grouped_aggregate
    from presto_tpu.ops.filter_project import filter_page
    from presto_tpu.ops.join import probe_join

    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[14])
    # the chain the executor runs, proved in a query's scope (PR 36),
    # with what its stages signed handed to the operators by hand; and
    # the chain lowered without the plan's intervals, which proves
    # nothing: the program from before there were proofs
    with ex._proving() if proving else contextlib.nullcontext():
        lowered = ex._lower(root)
    assert [s.kind for s in lowered.stages] == [
        "filter", "compact", "probe", "agg_partial"]
    assert lowered.arith_counts() == ((0, 6) if proving else (0, 0))
    assert lowered.stages[2].params.kind == "inner"
    flt, cmp_, probe, agg = (s.params for s in lowered.stages)

    def by_hand(page, consts):
        with jax.named_scope("op:Filter"):
            page = filter_page(page, flt.predicate, flt.proven)
        with jax.named_scope("op:Filter"):
            page, live = compact_page(page, page.capacity >> cmp_.k)
        cap_out = page.capacity
        with jax.named_scope("op:Join"):
            page = probe_join(
                consts["build_0"], page, list(probe.left_keys),
                key_domains=list(probe.key_domains), kind="inner",
                build_output=list(range(probe.build_arity)),
                null_safe=probe.null_safe, null_aware=probe.null_aware)
        with jax.named_scope("op:Aggregation"):
            page = grouped_aggregate(
                page, list(agg.group_exprs), list(agg.aggs), agg.max_groups,
                key_domains=list(agg.key_domains), mode="partial",
                presorted=agg.presorted, proven=agg.proven,
                lane_rows=agg.lane_rows)
        return page, live > cap_out

    consts = {"build_0": ex._materialize_build(lowered.joins[0])}
    page = next(iter(ex._source_pages(lowered.leaf)))
    name = lowered.name()
    texts = [jax.jit(local._named(f, name)).lower(page, consts).as_text()
             for f in (lowered.fn(), by_hand)]
    assert texts[0] == texts[1]


# -- the gate ----------------------------------------------------------------

def test_compact_k():
    assert chain_mod._compact_k(0.0119) == 5  # q14: 2.38% fits 1/32
    assert chain_mod._compact_k(1 / 64) == 5  # exactly 2 x share = 1/32
    assert chain_mod._compact_k(0.0625) == 3
    assert chain_mod._compact_k(0.068) == 0  # q6's: under 3 is not worth it
    assert chain_mod._compact_k(0.537) == 0  # q3's lineitem filter
    assert chain_mod._compact_k(1.0) == 0
    # inside a probe the floor is 2 (PERF.md, PR 31)
    assert chain_mod.COMPACT_MIN_K == {False: 3, True: 2}
    assert chain_mod._compact_k(0.0784, floor=2) == 2  # q3's probe, SF1
    assert chain_mod._compact_k(0.068, floor=2) == 2
    assert chain_mod._compact_k(0.126, floor=2) == 0  # 1/4 < 2 x share


@pytest.mark.parametrize("q", [1, 6])
def test_gate_leaves_other_chains_as_they_were(env, q):
    """q6's and q1's chains: no compaction point, so the chain
    gives the program of the chain that may never compact, to the
    letter of its lowered text (PERF.md, PR 26 and PR 30, compared all
    of these queries' programs with the parent's), under the name it
    had."""
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[q])
    lowered = ex._lower(root)
    assert not lowered.compacts
    name = lowered.name()
    assert name == {1: "chain_leaf_filter_agg_k2a8",
                    6: "chain_leaf_filter_agg_k0a1"}[q]
    built = [lowered.fn(), ex._lower(root, 0).fn()]
    consts = {f"build_{i}": ex._materialize_build(j)
              for i, j in enumerate(lowered.joins)}
    page = next(iter(ex._source_pages(lowered.leaf)))
    texts = [jax.jit(local._named(f, name)).lower(page, consts).as_text(
        debug_info=True) for f in built]
    assert texts[0] == texts[1]
    assert f"module @jit_{name}" in texts[0]
    assert "filter:compact" not in texts[0]


def _over(node, leaf_of):
    """The chain rooted at ``node`` over another leaf."""
    import dataclasses

    if chain_mod.chain_leaf(node) is node:
        return leaf_of(node)
    field = "left" if isinstance(node, JoinNode) else "source"
    return dataclasses.replace(
        node, **{field: _over(getattr(node, field), leaf_of)})


def test_gate_wants_a_scan_a_partial_and_a_selective_step(env):
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[14])
    lowered = ex._lower(root)
    assert _compaction(lowered) == ("probe", 5)
    at = [s.kind for s in lowered.stages].index("compact")
    assert lowered.stages[at + 1].node is root.source
    assert not ex._lower(root, compact_k=0).compacts
    # no filter in front of the probe, and a probe that keeps every row
    bare = _chain_root(runner, "select sum(l_extendedprice) from lineitem, "
                               "part where l_partkey = p_partkey")
    assert not ex._lower(bare).compacts
    assert not ex._lower(bare, compact_k=5).compacts
    # no page is held, so the partial may be of any size
    ex._agg_overrides[root] = 1 << 26
    assert ex._lower(root).compacts
    ex._agg_overrides[root] = 1
    # the consumer has to be one that can start again: a partial
    probe = root.source
    assert isinstance(probe, JoinNode) and not ex._lower(probe).compacts
    # after a miss the source is read again: it has to be a scan
    limited = _over(root, lambda scan: LimitNode(source=scan, count=1 << 40))
    ex._agg_overrides[limited] = 1
    assert isinstance(ex._lower(limited).leaf, LimitNode)
    assert not ex._lower(limited).compacts


def test_compacting_chain_is_named_and_scoped(env):
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[14])
    lowered = ex._lower(root)
    assert lowered.name() == "chain_leaf_filter_compact_probe_agg_k0a2"
    stage = lowered.fn()
    consts = {"build_0": ex._materialize_build(lowered.joins[0])}
    page = next(iter(ex._source_pages(lowered.leaf)))
    text = jax.jit(stage).lower(page, consts).as_text(debug_info=True)
    # the compaction is the filter's: no scope of its own at op: level
    assert "op:Filter/filter:compact" in text
    assert "op:Join" in text and "op:Aggregation" in text
    # one straight program: a conditional or a loop is an event of its
    # own in a device trace, under no scope (PERF.md, PR 26)
    assert "stablehlo.case" not in text and "stablehlo.while" not in text
