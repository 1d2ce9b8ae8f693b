"""Joins whose build side is not a primary key (PR 27): the spec's
``O_CUSTKEY`` population as an argument of the connector, the counters
of the expanding probe in the statement stats, and the left outer join
of TPC-H Q13 under the CSR leg against sqlite, with the null-extended
rows counted.  SF0.01 / SF0.1, XLA:CPU."""

import numpy as np
import pytest

from presto_tpu.catalog import Catalog
from presto_tpu.connectors.tpch import Tpch
from presto_tpu.exec.programs import ProgramRegistry
from presto_tpu.ops import join
from presto_tpu.runner import QueryRunner

from tests.oracle import assert_rows_match, load_oracle, run_oracle
from tests.tpch_queries import QUERIES

LEFT_JOIN = """
select c_custkey, o_orderkey
from customer left outer join orders on
    c_custkey = o_custkey and o_comment not like '%special%requests%'
"""


# -- the population --------------------------------------------------------

def test_orderless_third_draws_no_key_divisible_by_three():
    tpch = Tpch(sf=0.1, split_rows=1 << 20, orderless_third=True)
    n = tpch.n_customers
    keys = np.concatenate([tpch.generate_split("orders", s)["o_custkey"]
                           for s in range(tpch.num_splits("orders"))])
    assert len(keys) == tpch.n_orders
    assert keys.min() >= 1 and keys.max() <= n
    assert not (keys % 3 == 0).any()
    holders = len(np.unique(keys))
    assert abs(holders - (n - n // 3)) <= 0.01 * (n - n // 3)
    # ... which is what the connector's statistic has always claimed
    assert abs(tpch.column_ndv("orders", "o_custkey") - holders) <= 0.01 * n
    # lineitem is generated from the order index and does not change
    default = Tpch(sf=0.1, split_rows=1 << 20)
    a = tpch.generate_split("lineitem", 0)
    b = default.generate_split("lineitem", 0)
    assert all((a[c] == b[c]).all() for c in b)


def test_default_population_is_what_it_was():
    """The stored q03 answers of ``tpch_sf1`` and ``tpch_sf10`` were
    made from the uniform draw; the default keeps it, array for
    array."""
    tpch = Tpch(sf=0.1, split_rows=1 << 20)
    first = tpch.generate_split("orders", 0)
    order_idx = np.arange(len(first["o_orderkey"]))
    from presto_tpu.connectors.tpch import _seed, _uniform_int

    assert (first["o_custkey"] == _uniform_int(
        _seed("orders", "o_custkey"), order_idx, 1, tpch.n_customers)).all()
    # a third of the keys are divisible by 3: nearly nobody is orderless
    assert 0.32 < (first["o_custkey"] % 3 == 0).mean() < 0.35
    other = Tpch(sf=0.1, split_rows=1 << 20,
                 orderless_third=True).generate_split("orders", 0)
    for column in first:
        assert ((first[column] == other[column]).all()
                == (column != "o_custkey")), column


# -- the counters, through the coordinator ---------------------------------

@pytest.fixture(scope="module")
def served():
    from presto_tpu.client import StatementClient
    from presto_tpu.server.coordinator import CoordinatorServer

    tpch = Tpch(sf=0.01, orderless_third=True)
    catalog = Catalog()
    catalog.register("tpch", tpch)
    srv = CoordinatorServer(QueryRunner(catalog, programs=ProgramRegistry()))
    srv.start()
    try:
        yield StatementClient(srv.uri), tpch
    finally:
        srv.stop()


def _final_stats(client, sql):
    pages = []
    client.execute(sql, on_progress=pages.append)
    return pages[-1]


def test_q13_reports_its_retries_and_the_rows_it_emitted(served):
    from tests.pandas_oracle import load_frames

    client, tpch = served
    F = load_frames(tpch, {"customer": ["c_custkey"],
                           "orders": ["o_orderkey", "o_custkey", "o_comment"]})
    o = F["orders"]
    o = o[~o.o_comment.str.contains(r"special.*requests", regex=True)]
    left = F["customer"].merge(o, how="left", left_on="c_custkey",
                               right_on="o_custkey")
    stats = _final_stats(client, QUERIES[13])
    assert stats["expandRetries"] >= 1
    # every qualifying order once, and one row for each orderless customer
    assert stats["expandedRows"] == len(left)
    assert len(left) - len(o) == left.o_orderkey.isna().sum() >= 499
    # the read of each probe's total is one of the query's host reads
    assert stats["hostReads"] > stats["expandRetries"]


def test_q14_reports_no_expansion(served):
    client, _ = served
    stats = _final_stats(client, QUERIES[14])
    assert stats["expandRetries"] == 0 and stats["expandedRows"] == 0


# -- the outer join's share, under the chip's leg --------------------------

@pytest.fixture(scope="module")
def spec_population():
    tpch = Tpch(sf=0.01, split_rows=16384, orderless_third=True)
    catalog = Catalog()
    catalog.register("tpch", tpch)
    return catalog, load_oracle(tpch)


@pytest.fixture
def csr_leg(monkeypatch):
    taken = []

    def profitable():
        taken.append(join.resolve_direct_join())
        return taken[-1]

    join.set_direct_join_override(True)
    monkeypatch.setattr(join, "_direct_table_profitable", profitable)
    yield taken
    join.set_direct_join_override(None)


def test_q13_null_extends_every_customer_without_a_qualifying_order(
        spec_population, csr_leg):
    """Under the uniform draw nearly every customer holds an order and
    ``kind="left"`` is checked on almost nothing
    (``test_chip_legs.py``); under the spec's, a third of the probe's
    rows are null-extended."""
    catalog, oracle = spec_population
    runner = QueryRunner(catalog, programs=ProgramRegistry())
    q13 = runner.execute(QUERIES[13])
    assert_rows_match(q13.rows, run_oracle(oracle, QUERIES[13]),
                      ordered=False)
    assert csr_leg and all(csr_leg), "Q13 never built the starts table"
    (orderless,), = run_oracle(oracle, """
        select count(*) from customer where c_custkey not in (
            select o_custkey from orders
            where o_comment not like '%special%requests%')""")
    n = catalog._connectors["tpch"].n_customers
    assert abs(orderless - n // 3) <= 2  # 0.17% of orders are filtered
    assert (0, orderless) in [tuple(r) for r in q13.rows]
    joined = runner.execute(LEFT_JOIN)
    assert joined.expanded_rows == len(joined.rows) == q13.expanded_rows
    null_extended = [r for r in joined.rows if r[1] is None]
    assert len(null_extended) == orderless
    assert len({r[0] for r in null_extended}) == orderless
    assert_rows_match(joined.rows, run_oracle(oracle, LEFT_JOIN),
                      ordered=False)
