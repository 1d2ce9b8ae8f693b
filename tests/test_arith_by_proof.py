"""Arithmetic sized by proof (ISSUE 36): the intervals the plan already
computes (analysis/ranges.py, kernel_soundness.channel_values) reach
code generation, and the generated arithmetic takes the cheapest form
that is exact on them: a multiply, add, subtract or negation whose raw
result stays inside its lane is compiled without its wrap mask
(expr/compile.py), a sum whose page total stays inside int64 is
reduced in one lane and lifted to the limb state once a group
(ops/aggregate.py).  A guard that can fire is never dropped; no
answer differs."""

import dataclasses
import itertools

import jax
import numpy as np
import pytest

from presto_tpu import analysis
from presto_tpu.analysis import ranges
from presto_tpu.analysis.ranges import AbstractValue
from presto_tpu.catalog import Catalog
from presto_tpu.connectors.memory import MemoryConnector
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.exec.chain import lower_chain
from presto_tpu.exec.programs import ProgramRegistry, ir_signature
from presto_tpu.expr.compile import ExprCompiler
from presto_tpu.expr.ir import Call, ColumnRef, Literal
from presto_tpu.ops import decimal128 as d128
from presto_tpu.ops.aggregate import grouped_aggregate, limb_sum_site
from presto_tpu.page import Page
from presto_tpu.planner.plan import AggregationNode, TableScanNode
from presto_tpu.runner import QueryRunner
from presto_tpu.types import (
    BIGINT, INTEGER, SMALLINT, TINYINT, DecimalType,
)

from tests.oracle import assert_rows_match
from tests.tpch_queries import QUERIES

I64_MAX = (1 << 63) - 1
LANES = [(TINYINT, 7), (SMALLINT, 15), (INTEGER, 31), (BIGINT, 63)]


def known(lo, hi, may_null=False):
    return AbstractValue(lo, hi, may_null=may_null, known=True)


def proved(exprs, env):
    """The compiler's ``proven`` table as a stage builds it: the
    outcomes ``prove_sites`` signs, keyed by site."""
    return ranges.proven_table(exprs, ranges.prove_sites(exprs, env))


# ---------------------------------------------------------------------------
# q6's and q1's programs: proven and unproven give the same bits
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tpch_runner():
    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=0.01))
    return QueryRunner(catalog, programs=ProgramRegistry())


def _partial_agg(runner, q):
    node = runner.binder.plan(QUERIES[q])
    while not isinstance(node, AggregationNode):
        node = node.sources[0]
    while isinstance(node.sources[0], AggregationNode):
        node = node.sources[0]
    return dataclasses.replace(node, step="partial")


def _edge_page(scan, env, filter_mid):
    """A page of ``scan``'s channels holding the cross product of each
    channel's domain edges and one value in between that q1's and q6's
    filters keep (``filter_mid``, by column name)."""
    names = [scan.handle.columns[i].name for i in scan.columns]
    chans = scan.channels
    per = []
    for name, ch, v in zip(names, chans, env):
        assert v.known, name
        vals = {int(v.lo), int(v.hi)}
        if name in filter_mid:
            vals.add(filter_mid[name])
        per.append(sorted(vals))
    rows = list(itertools.product(*per))
    arrays = [np.asarray([r[i] for r in rows]) for i in range(len(chans))]
    return Page.from_arrays(
        arrays, [c.type for c in chans],
        dictionaries=[c.dictionary for c in chans])


# values the filters keep: q6 wants 1994, 0.05-0.07, under 24; q1 a
# ship date up to 1998-09-02
FILTER_MID = {"l_shipdate": 8800, "l_discount": 6, "l_quantity": 1200}


@pytest.mark.parametrize("q", [6, 1])
def test_proven_and_checked_programs_agree_on_the_domains_edges(
        tpch_runner, q):
    ex = tpch_runner.executor
    agg = _partial_agg(tpch_runner, q)
    with ex._proving():
        proven = ex._lower(agg)
        env = ex._intervals(proven.leaf)
    checked = lower_chain(agg, streaming=ex._streaming,
                          max_groups=ex._max_groups, compact_k=0)
    # outside a query's scope nothing is proved: the checked program
    assert ir_signature(ex._lower(agg, 0).signature()) \
        == ir_signature(checked.signature())
    assert isinstance(proven.leaf, TableScanNode)
    # every site of these two queries is proven, every limb sum too
    n_checked, n_proven = proven.arith_counts()
    assert n_checked == 0 and n_proven == {6: 4, 1: 8}[q]
    assert checked.arith_counts() == (0, 0)
    assert all(proven.stages[-1].params.sums())
    assert ir_signature(proven.signature()) \
        != ir_signature(checked.signature())

    page = _edge_page(proven.leaf, env, FILTER_MID)
    assert page.capacity >= 64
    got = jax.jit(proven.fn())(page, {})
    want = jax.jit(checked.fn())(page, {})
    assert bool(np.asarray(got.row_mask).any())
    np.testing.assert_array_equal(np.asarray(got.row_mask),
                                  np.asarray(want.row_mask))
    for g, w in zip(got.blocks, want.blocks):
        assert g.type == w.type
        np.testing.assert_array_equal(np.asarray(g.valid),
                                      np.asarray(w.valid))
        np.testing.assert_array_equal(np.asarray(g.data), np.asarray(w.data))

    # and the expressions alone: the same data, the same valid
    types = [b.type for b in page.blocks]
    dicts = [b.dictionary for b in page.blocks]
    for a in agg.aggs:
        if a.arg is None:
            continue
        d0, v0 = ExprCompiler(types, dicts).compile(a.arg)(page)
        d1, v1 = ExprCompiler(types, dicts, proved([a.arg], env)).compile(
            a.arg)(page)
        np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        assert bool(np.asarray(v1).all())


def test_the_proven_sum_is_one_lane_and_the_checked_one_limbs(tpch_runner):
    """What the two programs differ in, read from their jaxprs: q6's
    checked program divides row-sized arrays (the limb split, the
    multiply's check), the proven one divides nothing row-sized."""
    ex = tpch_runner.executor
    agg = _partial_agg(tpch_runner, 6)
    with ex._proving():
        proven = ex._lower(agg)
        env = ex._intervals(proven.leaf)
    checked = lower_chain(agg, streaming=ex._streaming,
                          max_groups=ex._max_groups, compact_k=0)
    page = _edge_page(proven.leaf, env, FILTER_MID)

    def row_sized_divisions(chain):
        jaxpr = jax.make_jaxpr(chain.fn())(page, {})
        n = 0
        todo = [jaxpr.jaxpr]
        while todo:
            j = todo.pop()
            for eqn in j.eqns:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    todo.append(sub)
                if eqn.primitive.name in ("div", "rem") and any(
                        getattr(v.aval, "shape", ())[:1] == (page.capacity,)
                        for v in eqn.outvars):
                    n += 1
        return n

    assert row_sized_divisions(checked) >= 4
    assert row_sized_divisions(proven) == 0


# ---------------------------------------------------------------------------
# the lane's last value is proven, the next one keeps its guard
# ---------------------------------------------------------------------------

def _site(fn, t, *args):
    return Call(type=t, fn=fn, args=tuple(args))


@pytest.mark.parametrize("t,bits", LANES, ids=[t.name for t, _ in LANES])
@pytest.mark.parametrize("fn", ["mul", "add", "sub"])
def test_an_interval_that_ends_on_the_lanes_edge_is_proven(t, bits, fn):
    top = (1 << bits) - 1
    a, b = ColumnRef(type=t, index=0), ColumnRef(type=t, index=1)
    e = _site(fn, t, a, b)
    # raw result reaches exactly 2^bits - 1 / one past it
    inside, outside = {
        "mul": ([known(0, 1), known(0, top)],
                [known(0, 2), known(0, (top + 1) // 2)]),
        "add": ([known(0, top - 1), known(0, 1)],
                [known(0, top - 1), known(0, 2)]),
        "sub": ([known(0, top - 1), known(-1, 0)],
                [known(0, top - 1), known(-2, 0)]),
    }[fn]
    assert ranges.site_proven(e, inside)
    assert not ranges.site_proven(e, outside)
    assert ranges.prove_sites([e], inside) == (True,)
    assert ranges.prove_sites([e], outside) == (False,)
    # the type contract alone is no proof, whatever its numbers
    assumed = [dataclasses.replace(v, known=False) for v in inside]
    assert not ranges.site_proven(e, assumed)
    # the lower edge too
    lo_in, lo_out = {
        "mul": ([known(-1, 0), known(0, top + 1 - 1)],
                [known(-1, 0), known(-(top + 1), 0)]),
        "add": ([known(-top, 0), known(-1, 0)],
                [known(-top, 0), known(-2, 0)]),
        "sub": ([known(-top, 0), known(0, 1)],
                [known(-top, 0), known(0, 2)]),
    }[fn]
    assert ranges.site_proven(e, lo_in)
    assert not ranges.site_proven(e, lo_out)

    # compiled: on the edge values the proven program's data and valid
    # are the checked program's; one past the edge the guard still fires
    dt = np.dtype(t.np_dtype)
    x = np.asarray([v.hi for v in inside][:1] * 2 + [inside[0].lo], dt)
    y = np.asarray([inside[1].hi, inside[1].lo, inside[1].lo], dt)
    page = Page.from_arrays([x, y], [t, t])
    d0, v0 = ExprCompiler([t, t], [None, None]).compile(e)(page)
    d1, v1 = ExprCompiler([t, t], [None, None],
                          proved([e], inside)).compile(e)(page)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    assert bool(np.asarray(v0).all())
    wrap = Page.from_arrays(
        [np.asarray([outside[0].hi], dt),
         np.asarray([outside[1].hi if fn != "sub" else outside[1].lo], dt)],
        [t, t])
    _, v2 = ExprCompiler([t, t], [None, None],
                         proved([e], outside)).compile(e)(wrap)
    assert not bool(np.asarray(v2)[0])


@pytest.mark.parametrize("t,bits", LANES, ids=[t.name for t, _ in LANES])
def test_negation_and_division_sites(t, bits):
    a, b = ColumnRef(type=t, index=0), ColumnRef(type=t, index=1)
    lo = -(1 << bits)
    neg = _site("neg", t, a)
    assert ranges.site_proven(neg, [known(lo + 1, 5)])
    assert not ranges.site_proven(neg, [known(lo, 5)])
    div = _site("div", t, a, b)
    assert ranges.site_proven(div, [known(lo, 9), known(1, 7)])
    assert not ranges.site_proven(div, [known(0, 9), known(0, 7)])
    assert not ranges.site_proven(div, [known(0, 9), known(-3, 7)])
    # INT_MIN / -1 keeps its check; without INT_MIN it goes
    assert not ranges.site_proven(div, [known(lo, 9), known(-2, -1)])
    assert ranges.site_proven(div, [known(lo + 1, 9), known(-2, -1)])
    mod = _site("mod", t, a, b)
    assert ranges.site_proven(mod, [known(lo, 9), known(-7, -1)])
    assert not ranges.site_proven(mod, [known(0, 9), known(-7, 0)])


def test_decimal_rescale_guard_is_a_part_of_the_site():
    """``dec(18,0) + dec(18,4)`` up-scales its left operand by 10^4
    first: proven only where the up-scaled interval stays inside int64
    too, even if the sum would."""
    d0, d4 = DecimalType(18, 0), DecimalType(18, 4)
    e = _site("add", d4, ColumnRef(type=d0, index=0),
              ColumnRef(type=d4, index=1))
    assert ranges.site_proven(e, [known(0, 10 ** 14), known(0, 10 ** 17)])
    assert not ranges.site_proven(
        e, [known(0, 10 ** 15), known(-(10 ** 18), 0)])


def test_coerced_branches_are_scaled_before_they_join():
    """CASE / IF / COALESCE bring their branches to the result's scale:
    the interval of ``case when c then dec(12,2) else 0`` is in cents,
    and an integer branch of 5 is 500 there."""
    d2 = DecimalType(12, 2)
    c = Call(type=BIGINT, fn="eq", args=(ColumnRef(type=BIGINT, index=0),
                                         Literal(type=BIGINT, value=1)))
    e = Call(type=d2, fn="case",
             args=(c, ColumnRef(type=d2, index=1),
                   Literal(type=BIGINT, value=5)))
    v = ranges.eval_expr(e, [known(0, 1), known(10, 20)])
    assert (v.lo, v.hi, v.known) == (10, 500, True)
    e = Call(type=d2, fn="if", args=e.args)
    v = ranges.eval_expr(e, [known(0, 1), known(10, 20)])
    assert (v.lo, v.hi) == (10, 500)


def test_an_unguarded_kernel_that_escapes_its_lane_is_the_whole_lane():
    """An up-scaling decimal cast has no guard: where its interval
    escapes int64 its lanes wrap and stay valid, so nothing narrower
    than the lane is known of them (add/sub/mul clamp, because their
    guard NULLs the escaped lanes)."""
    d0, d6 = DecimalType(18, 0), DecimalType(18, 6)
    cast = Call(type=d6, fn="cast_decimal",
                args=(ColumnRef(type=d0, index=0),))
    v = ranges.eval_expr(cast, [known(0, 10 ** 17)])
    assert (v.lo, v.hi) == ranges.I64
    mul = _site("mul", BIGINT, ColumnRef(type=BIGINT, index=0),
                ColumnRef(type=BIGINT, index=0))
    v = ranges.eval_expr(mul, [known(0, 10 ** 17)])
    assert (v.lo, v.hi) == (0, I64_MAX)


# ---------------------------------------------------------------------------
# through the runner: tables whose domains prove, admit, and change
# ---------------------------------------------------------------------------

def _mem_runner(tables):
    """``tables``: name -> (type, values, domain or None)."""
    mem = MemoryConnector()
    for name, (typ, values, dom) in tables.items():
        ids = np.arange(len(values), dtype=np.int64)
        page = Page.from_arrays([ids, values], [BIGINT, typ])
        mem.create_table(name, [("id", BIGINT), ("x", typ)], [page],
                         domains={"x": dom} if dom else None)
    catalog = Catalog()
    catalog.register("mem", mem)
    return QueryRunner(catalog, programs=ProgramRegistry()), mem


EDGE = [I64_MAX, -(1 << 63), 0, 1, -1, 4 * 10 ** 18, -(4 * 10 ** 18)]


@pytest.fixture
def unvalidated():
    """The harness's always-on kernel validation refuses a plan whose
    declared domain proves a wrap (an error where a deployment only
    NULLs the lane); the executor must be right without it."""
    analysis.set_kernel_validation(False)
    yield
    analysis.set_kernel_validation(None)


@pytest.mark.parametrize("dom", [None, (-(1 << 63), I64_MAX)],
                         ids=["no_domain", "domain_admits_the_wrap"])
def test_operands_that_admit_the_wrap_keep_every_guard(dom, unvalidated):
    """tests/test_overflow_semantics.py's tables declare no domain, so
    every one of its cases runs today's checked program; and a declared
    domain that admits the wrap proves nothing either."""
    runner, _ = _mem_runner({"edge": (BIGINT, EDGE, dom)})
    for sql, want in [
            ("x + 1", [None if v == I64_MAX else v + 1 for v in EDGE]),
            ("x - 1", [None if v == -(1 << 63) else v - 1 for v in EDGE]),
            ("x * 3", [v * 3 if abs(v * 3) <= I64_MAX else None
                       for v in EDGE]),
            ("-x", [None if v == -(1 << 63) else -v for v in EDGE])]:
        res = runner.execute(f"select id, {sql} from edge order by id")
        assert [r[1] for r in res.rows] == want, sql
        assert res.arith_proven == 0 and res.arith_checked >= 1, sql


WIDE = [9 * 10 ** 17] * 20 + [123456789, -987654321, 1]


def test_a_sum_whose_bound_crosses_int64_keeps_its_limbs(unvalidated):
    """9e17 a row proves a one-lane sum for ten rows, not for a page:
    no proof is taken and the limbs give the exact 1.8e19."""
    t = DecimalType(18, 0)
    runner, _ = _mem_runner(
        {"wide": (t, WIDE, (-987654321, 9 * 10 ** 17))})
    assert ranges.sum_lane_rows(known(-987654321, 9 * 10 ** 17)) == 0
    res = runner.execute("select sum(x) from wide")
    assert int(res.rows[0][0]) == sum(WIDE) > I64_MAX
    assert res.arith_checked == 1 and res.arith_proven == 0
    # a bound that does fit is taken, and gives the same exact integer
    small = [10 ** 12, -5, 7] * 9
    runner, _ = _mem_runner({"small": (t, small, (-5, 10 ** 12))})
    assert ranges.sum_lane_rows(known(-5, 10 ** 12)) == 1 << 23
    res = runner.execute("select sum(x) from small")
    assert int(res.rows[0][0]) == sum(small)
    assert res.arith_checked == 0 and res.arith_proven == 1


def test_a_page_wider_than_the_proof_takes_the_limbs():
    """``lane_rows`` is the capacity the proof holds for: the program
    compares it with the page it is traced for, and both forms give
    the same canonical limb state."""
    t = DecimalType(18, 0)
    vals = np.asarray([9 * 10 ** 17] * 40, np.int64)  # sums past 2^63
    page = Page.from_arrays([vals], [t])
    from presto_tpu.expr.ir import AggCall
    from presto_tpu.ops.aggregate import output_type

    agg = AggCall(fn="sum", arg=ColumnRef(type=t, index=0), type=None)
    agg = dataclasses.replace(agg, type=output_type(agg))
    assert limb_sum_site(agg)
    limbs = grouped_aggregate(page, [], [agg], 1, mode="partial")
    same = grouped_aggregate(page, [], [agg], 1, mode="partial",
                             lane_rows=(page.capacity // 2,))
    np.testing.assert_array_equal(np.asarray(limbs.blocks[0].data),
                                  np.asarray(same.blocks[0].data))
    assert d128.decode_py(np.asarray(limbs.blocks[0].data)) == [36 * 10 ** 18]
    # a page the proof covers: one lane, the same limbs as row by row
    ok = Page.from_arrays([np.asarray([10 ** 12, -3] * 8, np.int64)], [t])
    a = grouped_aggregate(ok, [], [agg], 1, mode="partial")
    b = grouped_aggregate(ok, [], [agg], 1, mode="partial",
                          lane_rows=(ok.capacity,))
    np.testing.assert_array_equal(np.asarray(a.blocks[0].data),
                                  np.asarray(b.blocks[0].data))
    assert d128.decode_py(np.asarray(b.blocks[0].data)) == [8 * (10 ** 12 - 3)]


F = 922337203685477580  # 10 * F fits int64, 11 * F does not


def _row(i, x):
    return Page.from_arrays([np.asarray([i], np.int64),
                             np.asarray([x], np.int64)], [BIGINT, BIGINT])


def test_an_append_outside_the_domain_widens_it_and_the_guard_returns(
        unvalidated):
    """The SAME statement text each time: the runner's plan is the one
    it cached when x lay in [0, 10] (an append through the connector
    clears no plan), and the chain is proved by the domain the
    connector declares when the statement runs, not by the plan's."""
    runner, mem = _mem_runner({"t": (BIGINT, [0, 3, 10], (0, 10))})
    sql = f"select id, x * {F} from t order by id"
    res = runner.execute(sql)
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F]
    assert (res.arith_checked, res.arith_proven) == (0, 1)
    plan = runner._plans[sql]
    proven = runner.programs.callable_count()

    # inside the domain: nothing widens, nothing new is compiled for
    mem.append_pages("t", [_row(3, 7)])
    assert mem.column_domain("t", "x") == (0, 10)
    res = runner.execute(sql)
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F, 7 * F]
    assert (res.arith_checked, res.arith_proven) == (0, 1)
    assert runner.programs.callable_count() == proven

    # outside: the domain widens, the next statement's program carries
    # the guard again (another signature: no stale program), and the
    # row that wraps is NULL, not a wrong number
    mem.append_pages("t", [_row(4, 11)])
    assert mem.column_domain("t", "x") == (0, 11)
    res = runner.execute(sql)
    assert runner._plans[sql] is plan  # bound when x was in [0, 10]
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F, 7 * F, None]
    assert (res.arith_checked, res.arith_proven) == (1, 0)
    assert runner.programs.callable_count() > proven


def test_a_write_through_another_runner_is_seen_too(unvalidated):
    """Two runners on one catalog: an INSERT through the second clears
    no plan of the first."""
    runner, mem = _mem_runner({"t": (BIGINT, [0, 3, 10], (0, 10))})
    other = QueryRunner(runner.catalog, programs=ProgramRegistry())
    sums = "select sum(x) from t"
    sql = f"select id, x * {F} from t order by id"
    assert runner.execute(sql).arith_proven == 1
    assert runner.execute(sums).rows == [(13,)]
    other.execute("insert into t values (3, 11)")
    assert mem.column_domain("t", "x") == (0, 11)
    res = runner.execute(sql)
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F, None]
    assert (res.arith_checked, res.arith_proven) == (1, 0)
    assert runner.execute(sums).rows == [(24,)]


def test_rows_appended_after_the_evidence_was_read_are_the_next_statements(
        unvalidated, monkeypatch):
    """A scan runs over the splits counted when its domains were read:
    a writer that lands between the chain's lowering and the scan's
    first page adds nothing to THIS statement, whose program was proved
    without its row; the next statement sees the row and its guard."""
    runner, mem = _mem_runner({"t": (BIGINT, [0, 3, 10], (0, 10))})
    ex = runner.executor
    sql = f"select id, x * {F} from t order by id"
    program, landed = ex._chain_program, []

    def writer_lands_here(chain):
        if not landed:
            landed.append(True)
            mem.append_pages("t", [_row(3, 11)])
        return program(chain)

    monkeypatch.setattr(ex, "_chain_program", writer_lands_here)
    res = runner.execute(sql)
    assert landed and mem.num_splits("t") == 2
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F]
    assert (res.arith_checked, res.arith_proven) == (0, 1)
    res = runner.execute(sql)
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F, None]
    assert (res.arith_checked, res.arith_proven) == (1, 0)


def test_a_write_during_the_evidence_read_proves_nothing(
        unvalidated, monkeypatch):
    """The domains are read between two reads of the table's version:
    where they differ, the domain is nobody's and every guard stays."""
    runner, mem = _mem_runner({"t": (BIGINT, [0, 3, 10], (0, 10))})
    domain, landed = mem.column_domain, []

    def writer_lands_here(table, column):
        dom = domain(table, column)
        if column == "x" and not landed:
            landed.append(True)
            mem.append_pages("t", [_row(3, 11)])
        return dom

    monkeypatch.setattr(mem, "column_domain", writer_lands_here)
    # (the binder asks for the domain too: the plan is cached by the
    # first statement, then the writer is armed)
    sql = f"select id, x * {F} from t order by id"
    landed.append(True)
    assert runner.execute(sql).arith_proven == 1
    landed.clear()
    res = runner.execute(sql)
    assert landed and mem.num_splits("t") == 2
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F]
    assert (res.arith_checked, res.arith_proven) == (1, 0)


def test_a_connector_without_versions_proves_nothing(unvalidated):
    """What a connector owes for its domain to count as proof: a
    ``table_version`` to bind it to.  Without one (the remote
    connector's cached meta, the shard store) the domain still packs
    keys, and drops no guard."""
    runner, mem = _mem_runner({"t": (BIGINT, [0, 3, 10], (0, 10))})

    class Unversioned:
        def __getattr__(self, name):
            if name == "table_version":
                raise AttributeError(name)
            return getattr(mem, name)

    catalog = Catalog()
    catalog.register("mem", Unversioned())
    runner = QueryRunner(catalog, programs=ProgramRegistry())
    res = runner.execute(f"select id, x * {F} from t order by id")
    assert [r[1] for r in res.rows] == [0, 3 * F, 10 * F]
    assert (res.arith_checked, res.arith_proven) == (1, 0)


def test_a_row_count_bound_at_plan_time_is_no_evidence(unvalidated):
    """``count(*)`` of three rows times a third of int64 fits; of four
    it wraps.  The plan's row count is the bind's, so it proves
    nothing: the cached plan's multiply keeps its guard and the fourth
    row's statement answers NULL."""
    third = I64_MAX // 3
    runner, mem = _mem_runner({"t": (BIGINT, [0, 3, 10], (0, 10))})
    sql = f"select count(*) * {third} from t"
    res = runner.execute(sql)
    assert res.rows == [(3 * third,)]
    assert res.arith_checked >= 1 and res.arith_proven == 0
    mem.append_pages("t", [_row(3, 7)])
    assert runner.execute(sql).rows == [(None,)]


def test_outside_a_querys_scope_nothing_is_proved(tpch_runner):
    """A caller that pulls ``_pages`` without a query around it (a
    worker's fragment) has no evidence that is one table state for all
    its chains: it runs the checked programs."""
    from presto_tpu.exec import local

    ex = tpch_runner.executor
    agg = _partial_agg(tpch_runner, 6)
    before = local.arith_counts()
    pages = list(ex._pages(agg))
    assert pages and local.arith_counts() == before
    with ex._proving():
        assert ex._lower(agg).arith_counts() == (0, 4)
    assert ex._lower(agg).arith_counts() == (0, 0)


def test_the_cells_pages_are_under_every_proven_sums_reach(tpch_runner):
    """``arithChecked`` counts a sum as proven for the pages its proof
    covers (``AggPartial.lane_rows``); a larger page would split row by
    row all the same.  The benchmark's pages are 2^23 rows, the
    narrowest proof of q1, q6, q14 and q3 reaches 2^26."""
    import glob
    import json

    ex = tpch_runner.executor
    reach = []
    for q in (1, 6, 14, 3):
        with ex._proving():
            params = ex._lower(_partial_agg(tpch_runner, q)).stages[-1].params
        reach += [r for r in params.lane_rows if r is not None]
    assert reach and min(reach) == 1 << 26
    split_rows = [json.load(open(f))["split_rows"]
                  for f in glob.glob("benchmark/configs/*.json")]
    assert split_rows and max(split_rows) <= 1 << 23


def test_the_signature_holds_outcomes_not_numbers(tpch_runner):
    """Two domains that prove the same sign the same chain; a domain
    that proves nothing signs another; no intervals at all, a third
    (nothing was proved, nothing is counted)."""
    ex = tpch_runner.executor
    agg = _partial_agg(tpch_runner, 6)

    def lowered(scale):
        def intervals(node):
            return [dataclasses.replace(v, lo=v.lo * scale, hi=v.hi * scale)
                    if v.known and v.lo >= 0 else v
                    for v in ex._intervals(node)]
        with ex._proving():
            return lower_chain(agg, streaming=ex._streaming,
                               max_groups=ex._max_groups,
                               intervals=intervals)

    base, twice, huge = lowered(1), lowered(2), lowered(10 ** 9)
    assert ir_signature(base.signature()) == ir_signature(twice.signature())
    assert ir_signature(base.signature()) != ir_signature(huge.signature())
    assert huge.arith_counts()[0] >= 1
    none = lower_chain(agg, streaming=ex._streaming,
                       max_groups=ex._max_groups)
    assert none.arith_counts() == (0, 0)
    assert ir_signature(none.signature()) not in (
        ir_signature(base.signature()), ir_signature(huge.signature()))


def test_explain_validate_says_which_it_was(tpch_runner):
    res = tpch_runner.execute("EXPLAIN (TYPE VALIDATE) " + QUERIES[6])
    text = res.rows[0][1]
    assert "arithmetic:" in text
    assert "mul($" in text and " proven" in text and "checked" not in text
    res = tpch_runner.execute("EXPLAIN (TYPE VALIDATE) " + QUERIES[14])
    assert "div(" in res.rows[0][1] and " checked" in res.rows[0][1]


# ---------------------------------------------------------------------------
# the evidence: the declared domains hold for what the generator emits
# ---------------------------------------------------------------------------

MONEY = {"lineitem": ["l_extendedprice"], "orders": ["o_totalprice"],
         "part": ["p_retailprice"], "partsupp": ["ps_supplycost"],
         "customer": ["c_acctbal"], "supplier": ["s_acctbal"]}


@pytest.mark.parametrize("sf", [0.01, 1])
@pytest.mark.parametrize("table", sorted(MONEY))
def test_generated_money_lies_inside_its_declared_domain(table, sf):
    conn = Tpch(sf=sf)
    for col in MONEY[table]:
        lo, hi = conn.column_domain(table, col)
        seen_lo, seen_hi = None, None
        for s in range(conn.num_splits(table)):
            vals = conn.generate_split(table, s)[col]
            seen_lo = int(vals.min()) if seen_lo is None \
                else min(seen_lo, int(vals.min()))
            seen_hi = int(vals.max()) if seen_hi is None \
                else max(seen_hi, int(vals.max()))
        assert lo <= seen_lo and seen_hi <= hi, (col, seen_lo, seen_hi)
        # and the bound is the formula's, not a loose guess: SF1 comes
        # within a tenth of a percent of the columns drawn uniformly
        if sf == 1 and col != "o_totalprice":
            assert seen_hi >= hi - abs(hi) // 1000, (col, seen_hi, hi)


@pytest.fixture(scope="module")
def oracle():
    from tests.oracle import load_oracle

    return load_oracle(Tpch(sf=0.01))


@pytest.mark.parametrize("q", [1, 6, 14, 3])
def test_queries_under_the_range_sanitizer(q, oracle):
    """With the sanitizer on, every page that crosses a stage boundary
    is held to its predicted interval: a declared domain the
    generator's data escapes fails loudly here."""
    from tests.oracle import run_oracle

    catalog = Catalog()
    catalog.register("tpch", Tpch(sf=0.01))
    runner = QueryRunner(catalog, programs=ProgramRegistry())
    analysis.set_range_sanitizer(True)
    try:
        res = runner.execute(QUERIES[q])
    finally:
        analysis.set_range_sanitizer(None)
    # q14's final division has a sum for a divisor; q3's sum runs over
    # sorted runs (8192 groups), where the one-lane form is not taken
    assert res.arith_proven >= 2
    assert res.arith_checked == {1: 0, 6: 0, 14: 1, 3: 1}[q]
    assert_rows_match(res.rows, run_oracle(oracle, QUERIES[q]),
                      ordered=q != 6)
