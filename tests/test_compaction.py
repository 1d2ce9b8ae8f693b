"""Selection compaction in front of a probe (ops/filter_project
``compact_page``, exec/chain ``lower_chain``'s ``compact`` stage,
exec/local ``_chain_pages``): the primitive keeps order, validity and
count; a chain that compacts answers as the chain that does not,
whether its pages fit or the chain has to run again whole; chains the
gate leaves alone build the program they always built."""

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
    """Every chain ``ex`` lowers from here on compacts at ``k``."""
    lower = ex._lower
    monkeypatch.setattr(ex, "_lower",
                        lambda node, compact_k=None: lower(node, k))


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
    page: the chain runs again under the program that does not
    compact, and the answer is the same."""
    runner, frames = env
    _force_k(monkeypatch, runner.executor, 12)
    rows, (compacted, fallback) = _counts(runner, QUERIES[14] + " ")
    assert_rows_match(rows, PANDAS_QUERIES[14](frames), ordered=False)
    assert compacted == 0 and fallback == 4


@pytest.mark.parametrize("q", [1, 3, 6])
def test_other_queries_compact_nothing(env, q):
    runner, frames = env
    rows, counts = _counts(runner, QUERIES[q])
    assert_rows_match(rows, PANDAS_QUERIES[q](frames), ordered=False)
    assert counts == (0, 0)


def _programs(runner, root, k):
    """(today's program, the compacting one at ``k``, their consts,
    the chain's pages) through the lowered chain."""
    ex = runner.executor
    lowered = ex._lower(root, k)
    assert lowered.compacts and not lowered.uncompacted().compacts
    plain = jax.jit(lowered.uncompacted().fn())
    chain = jax.jit(lowered.fn())
    assert [type(j) for j in lowered.uncompacted().joins] == [JoinNode] == [
        type(j) for j in lowered.joins]
    consts = {"build_0": ex._materialize_build(lowered.joins[0])}
    pages = list(ex._source_pages(lowered.leaf))
    assert len(pages) == 4
    return plain, chain, consts, pages


def _compaction(lowered):
    """(kind of the stage the chain compacts in front of, k), or
    None."""
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
    the compacting program reports the miss, and ``_chain_pages`` then
    gives the partial aggregates of today's program, page for page."""
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[14])
    plain, chain, consts, pages = _programs(runner, root, 12)
    for page in pages:
        assert bool(chain(page, consts)[1])
    _force_k(monkeypatch, ex, 12)
    before = local.compact_counts()
    outs = list(ex._chain_pages(root))
    assert local.compact_counts() == (before[0], before[1] + 4)
    assert len(outs) == len(pages)
    for got, page in zip(outs, pages):
        _same_page(got, plain(page, consts))


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


# -- the gate ----------------------------------------------------------------

def test_compact_k():
    assert chain_mod._compact_k(0.0119) == 5  # q14: 2.38% fits 1/32
    assert chain_mod._compact_k(1 / 64) == 5  # exactly 2 x share = 1/32
    assert chain_mod._compact_k(0.0625) == 3
    assert chain_mod._compact_k(0.068) == 0  # q6's: under 3 is not worth it
    assert chain_mod._compact_k(0.537) == 0  # q3's lineitem filter
    assert chain_mod._compact_k(1.0) == 0


@pytest.mark.parametrize("q", [1, 3, 6])
def test_gate_leaves_other_chains_as_they_were(env, q):
    """q6's, q1's and q3's chains: no compaction point, so the chain
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
                    3: "chain_leaf_filter_probe_agg_k3a1",
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


def test_gate_wants_a_scan_a_small_partial_and_a_filter(env):
    runner, _ = env
    ex = runner.executor
    root = _chain_root(runner, QUERIES[14])
    lowered = ex._lower(root)
    assert _compaction(lowered) == ("probe", 5)
    at = [s.kind for s in lowered.stages].index("compact")
    assert lowered.stages[at + 1].node is root.source
    assert not ex._lower(root, compact_k=0).compacts
    # no filter in front of the probe
    bare = _chain_root(runner, "select sum(l_extendedprice) from lineitem, "
                               "part where l_partkey = p_partkey")
    assert not ex._lower(bare).compacts
    assert not ex._lower(bare, compact_k=5).compacts
    # its pages are held until the last: they have to be small
    ex._agg_overrides[root] = chain_mod.COMPACT_MAX_GROUPS + 1
    assert not ex._lower(root).compacts
    ex._agg_overrides[root] = 1
    assert ex._lower(root).compacts
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
