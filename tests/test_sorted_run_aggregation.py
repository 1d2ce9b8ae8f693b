"""The sort path of grouped aggregation: every aggregate's inputs ride
the key's sort (``ops/aggregate._ride_the_sort``), in as many sorts of
the same key as their widths need (``_batches``), and the reductions
walk the sorted order.  Checked against a plain group-by written here,
over random pages with NULL keys and values, aggregate filters and dead
rows; and pinned structurally: no row-sized scatter and no gather of
rows by a permutation in the programs of the sort branch and of
``merge_aggregate``.
"""

import decimal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.exec.local import concat_pages_device
from presto_tpu.exec.programs import ProgramRegistry
from presto_tpu.expr.ir import AggCall, Call, ColumnRef, Literal
from presto_tpu.ops import aggregate as A
from presto_tpu.page import Block, Page
from presto_tpu.runner import QueryRunner
from presto_tpu.types import (
    BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, DecimalType,
)

D122 = DecimalType(12, 2)
D184 = DecimalType(18, 4)
ROWS = 2048
#: channels of the random page: key, key2, i32, i64, dec(12,2),
#: dec(18,4), filter flag
TYPES = [BIGINT, DATE, INTEGER, BIGINT, D122, D184, BIGINT]


def _col(i):
    return ColumnRef(TYPES[i], i)


def _filter():
    """``flag > 0``, NULL where the flag is."""
    return Call(type=BOOLEAN, fn="gt",
                args=(_col(6), Literal(type=BIGINT, value=0)))


def _aggs():
    """count_star, count, sum and avg over int32, int64, a short decimal
    and a limb sum (decimal(18,4) sums to decimal(38,4)); two filtered."""
    return [
        AggCall("count_star", None, BIGINT),
        AggCall("count", _col(3), BIGINT),
        AggCall("sum", _col(2), BIGINT),
        AggCall("sum", _col(3), BIGINT, filter=_filter()),
        AggCall("avg", _col(3), DOUBLE),
        AggCall("sum", _col(4), DecimalType(22, 2)),
        AggCall("avg", _col(4), D122),
        AggCall("sum", _col(5), DecimalType(38, 4), filter=_filter()),
        AggCall("count_star", None, BIGINT, filter=_filter()),
    ]


def _page(seed, groups, rows=ROWS, sort_by_key=False):
    rng = np.random.default_rng(seed)
    k1 = rng.integers(0, groups, rows)
    k1v = rng.random(rows) > 0.05
    k2 = rng.integers(100, 103, rows).astype(np.int32)
    k2v = rng.random(rows) > 0.05
    i32 = rng.integers(-1000, 1000, rows).astype(np.int32)
    i64 = rng.integers(-10 ** 12, 10 ** 12, rows)
    d12 = rng.integers(-10 ** 10, 10 ** 10, rows)
    d18 = rng.integers(-9 * 10 ** 17, 9 * 10 ** 17, rows)
    flag = rng.integers(-1, 2, rows)
    datas = [k1, k2, i32, i64, d12, d18, flag]
    valids = [k1v, k2v] + [rng.random(rows) > 0.1 for _ in range(5)]
    live = rng.random(rows) > 0.15
    if sort_by_key:
        # streaming input: equal keys contiguous (NULLs as one run)
        order = np.lexsort((np.where(k1v, k1, -1),))
        datas = [d[order] for d in datas]
        valids = [v[order] for v in valids]
        live = live[order]
    blocks = tuple(Block(jnp.asarray(d), jnp.asarray(v), t)
                   for d, v, t in zip(datas, valids, TYPES))
    return Page(blocks, jnp.asarray(live)), (datas, valids, live)


def _reference(host, keys, aggs):
    """key tuple -> partial states, by a plain loop over the live rows."""
    datas, valids, live = host
    out = {}
    for r in np.nonzero(live)[0]:
        k = tuple(int(datas[i][r]) if valids[i][r] else None for i in keys)
        states = out.setdefault(k, [[0, 0] for _ in aggs])
        for st, a in zip(states, aggs):
            if a.filter is not None and not (
                    valids[6][r] and datas[6][r] > 0):
                continue
            if a.fn == "count_star":
                st[1] += 1
                continue
            i = a.arg.index
            if not valids[i][r]:
                continue
            if a.fn != "count":
                st[0] += int(datas[i][r])
            st[1] += 1
    return out


def _got(page, nkeys, aggs):
    """key tuple -> partial states of an output page, as integers."""
    out = {}
    for row in page.to_pylist(decode_strings=False):
        k, vals = tuple(row[:nkeys]), list(row[nkeys:])
        states = []
        for a in aggs:
            if a.fn in ("count", "count_star"):
                states.append([0, vals.pop(0)])
                continue
            s, n = vals.pop(0), vals.pop(0)
            if isinstance(s, decimal.Decimal):
                s = int(s.scaleb(a.arg.type.scale or 0))
            states.append([int(s), n])
        out[k] = states
    return out


#: key channels and their domains: wider than any capacity asked for
#: below, so the packed keys take the sort path and not packed-direct
KEYS = {
    # one key with a domain: packed exactly, keys unpacked from the sort
    "packed": ([0], [(0, 9999)]),
    # the same key without one: hashed, the key columns ride the sort
    "hashed": ([0], [None]),
    # two keys packed
    "two": ([0, 1], [(0, 9999), (100, 102)]),
}


@pytest.fixture
def masked_reduce(request, monkeypatch):
    """The masked broadcast-reduce that small group counts take on a
    TPU (``SMALL_SEG_LIMIT``), over the sorted rows; XLA:CPU takes
    scatter-add otherwise."""
    if request.param:
        monkeypatch.setattr(A, "_masked_segments_profitable", lambda: True)
    return request.param


@pytest.mark.parametrize("masked_reduce", [False, True], indirect=True,
                         ids=["scatter", "masked"])
@pytest.mark.parametrize("groups", [40, 700], ids=["few", "many"])
@pytest.mark.parametrize("keys", sorted(KEYS))
@pytest.mark.parametrize("seed", [0, 1])
def test_partials_equal_a_plain_group_by(keys, groups, seed, masked_reduce):
    cols, domains = KEYS[keys]
    page, host = _page(seed, groups)
    aggs = _aggs()
    # a capacity just past the groups present (few: under the masked
    # limit; many: over it)
    cap = 64 if groups < 100 else 2048
    if keys == "two":
        cap *= 4
    out, n = jax.jit(lambda p: A.grouped_aggregate(
        p, [_col(i) for i in cols], aggs, cap, key_domains=domains,
        mode="partial", return_count=True))(page)
    want = _reference(host, cols, aggs)
    assert int(n) == len(want)
    assert _got(out, len(cols), aggs) == want


@pytest.mark.parametrize("keys", ["packed", "hashed"])
def test_merge_of_partials_equals_the_whole(keys):
    cols, domains = KEYS[keys]
    aggs = _aggs()
    a, ha = _page(3, 500)
    b, hb = _page(4, 500)
    dom = domains
    parts = [A.grouped_aggregate(p, [_col(0)], aggs, 1024, key_domains=dom,
                                 mode="partial") for p in (a, b)]
    merged, n = jax.jit(lambda x, y: A.merge_aggregate(
        concat_pages_device([x, y]), 1, aggs, 2048, key_domains=dom,
        mode="partial", return_count=True))(*parts)
    host = tuple(
        [np.concatenate([x, y]) for x, y in zip(ha[i], hb[i])]
        for i in range(2)) + (np.concatenate([ha[2], hb[2]]),)
    want = _reference(host, cols, aggs)
    assert int(n) == len(want)
    assert _got(merged, 1, aggs) == want


def test_streaming_path_equals_the_sort_path():
    page, host = _page(5, 300, sort_by_key=True)
    aggs = _aggs()
    dom = KEYS["packed"][1]
    streamed = A.grouped_aggregate(page, [_col(0)], aggs, 1024,
                                   key_domains=dom, mode="partial",
                                   presorted=True)
    assert _got(streamed, 1, aggs) == _reference(host, [0], aggs)


@pytest.mark.parametrize("keys", ["packed", "two"])
def test_truncation_keeps_the_first_groups_exact(keys):
    """More groups than ``max_groups``: the count says so, and the
    groups kept (the first in key order, NULL first) are exact."""
    cols, domains = KEYS[keys]
    page, host = _page(6, 400)
    aggs = _aggs()
    out, n = A.grouped_aggregate(page, [_col(i) for i in cols], aggs, 100,
                                 key_domains=domains, mode="partial",
                                 return_count=True)
    want = _reference(host, cols, aggs)
    assert int(n) == len(want) > 100
    order = sorted(want, key=lambda k: tuple(
        (-1 if v is None else v) for v in k))
    assert _got(out, len(cols), aggs) == {k: want[k] for k in order[:100]}


def test_one_aggregate_a_sort_gives_the_same_pages(monkeypatch):
    """Batching is a matter of memory only: with every aggregate in a
    sort of its own the pages are the same, and the sorts many."""
    page, _ = _page(7, 600)
    aggs = _aggs()

    def run():
        runs0 = A.traced_sorted_runs()
        out = jax.jit(lambda p: A.grouped_aggregate(
            p, [_col(0)], aggs, 1024, key_domains=KEYS["packed"][1],
            mode="single"))(page)
        return out, A.traced_sorted_runs()[1] - runs0[1]

    wide, sorts = run()
    assert sorts == 1
    monkeypatch.setattr(A, "_SORT_STACK_BYTES", 0)
    narrow, many = run()
    assert many > sorts
    assert narrow.to_pylist() == wide.to_pylist()


def test_batches_follow_the_widths():
    """In order, within the lanes a sort may stack: flags share a lane,
    an aggregate wider than the budget rides alone."""
    bit, lane = (0, 1, False), (1, 1, False)
    assert A._batches([bit] * 40, 2) == [list(range(40))]  # two flag lanes
    assert A._batches([lane] * 4, 3) == [[0, 1], [2, 3]]
    assert A._batches([(2, 0, False)] * 3, 3) == [[0], [1], [2]]
    assert A._batches([(5, 0, False), lane], 3) == [[0], [1]]
    assert A._batches([], 1) == [[]]
    # the key columns of a hashed key ride the first sort
    assert A._batches([lane, lane], 3, first=(1, 1, False)) == [[0], [1]]


def test_lanes_per_sort_follow_the_rows():
    """Every lane of a 2^17-row page in one sort, up to 16; one lane a
    sort from 2^21 rows."""
    assert A._lanes_per_sort(1 << 17) == 16
    assert A._lanes_per_sort(1 << 15) == 64
    assert A._lanes_per_sort(1 << 21) == A._lanes_per_sort(1 << 23) == 1


@pytest.mark.parametrize("dtype", [
    jnp.int64, jnp.int32, jnp.int16, jnp.uint32, jnp.uint64, jnp.float64,
    jnp.float32])
def test_a_lane_rides_the_stack_losslessly(dtype):
    """Every dtype a lane has comes back from its int64 row bit for bit
    (NaN, negative zero and the extremes included)."""
    if jnp.issubdtype(dtype, jnp.floating):
        x = np.array([0.0, -0.0, np.nan, np.inf, -np.inf, 1.5,
                      np.finfo(dtype).min, np.finfo(dtype).tiny], dtype)
    else:
        info = np.iinfo(dtype)
        x = np.array([0, 1, info.min, info.max, info.max // 3], dtype)
    back = A._from_int64(A._to_int64(jnp.asarray(x)), dtype)
    assert back.dtype == x.dtype
    assert np.asarray(back).tobytes() == x.tobytes()


# ---------------------------------------------------------------------------
# structure: nothing row-sized is gathered by a permutation or scattered
# ---------------------------------------------------------------------------

def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _row_sized(closed, rows):
    """The gathers whose indices and the scatters whose operands are
    row-sized, and the number of sorts."""
    bad, sorts = [], 0
    for e in _eqns(closed.jaxpr):
        name = e.primitive.name
        if name == "sort":
            sorts += 1
        elif name == "gather":
            if e.invars[1].aval.shape[0] >= rows:
                bad.append((name, e.invars[1].aval.shape))
        elif name.startswith("scatter"):
            if e.invars[0].aval.shape[0] >= rows:
                bad.append((name, e.invars[0].aval.shape))
    return bad, sorts


@pytest.mark.parametrize("keys", sorted(KEYS))
def test_sort_branch_gathers_and_scatters_no_rows(keys):
    cols, domains = KEYS[keys]
    page, _ = _page(8, 700)
    aggs = _aggs()
    jaxpr = jax.make_jaxpr(lambda p: A.grouped_aggregate(
        p, [_col(i) for i in cols], aggs, 1024, key_domains=domains,
        mode="partial"))(page)
    bad, sorts = _row_sized(jaxpr, ROWS)
    assert not bad
    assert sorts >= 1


@pytest.mark.parametrize("keys", ["packed", "hashed"])
def test_merge_gathers_and_scatters_no_rows(keys):
    cols, domains = KEYS[keys]
    aggs = _aggs()
    parts = [A.grouped_aggregate(_page(s, 500)[0], [_col(0)], aggs, 1024,
                                 key_domains=domains, mode="partial")
             for s in (9, 10)]
    jaxpr = jax.make_jaxpr(lambda x, y: A.merge_aggregate(
        concat_pages_device([x, y]), 1, aggs, 1024,
        key_domains=domains, mode="partial"))(*parts)
    bad, sorts = _row_sized(jaxpr, 2048)
    assert not bad
    assert sorts >= 1


# ---------------------------------------------------------------------------
# the statement's counts
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch_runner():
    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=0.01))
    return QueryRunner(catalog, programs=ProgramRegistry())


@pytest.mark.parametrize("sql,sorted_path", [
    ("select l_orderkey, sum(l_quantity) from lineitem group by l_orderkey",
     True),
    ("select sum(l_quantity) from lineitem", False),
    ("select l_returnflag, count(*) from lineitem group by l_returnflag",
     False),
])
def test_statement_counts_its_sorted_runs(tpch_runner, sql, sorted_path):
    """``sortedRunAggs``: the sort-path aggregations the statement's
    programs ran, the same on a second run, when nothing traces; 0 for
    a global aggregation and for keys few enough to be their slots."""
    first = tpch_runner.execute(sql)
    again = tpch_runner.execute(sql + " ")
    assert (again.sorted_run_aggs, again.sorted_run_sorts) == (
        first.sorted_run_aggs, first.sorted_run_sorts)
    if sorted_path:
        assert again.sorted_run_aggs >= 1
        assert again.sorted_run_sorts >= again.sorted_run_aggs
    else:
        assert (again.sorted_run_aggs, again.sorted_run_sorts) == (0, 0)
