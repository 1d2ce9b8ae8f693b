"""Hand-written pandas programs for the TPC-H queries the cells run.

Copied from ``tests/pandas_oracle.py`` (q1, q3, q6, q14; the original
stays for the repo's own tests).  They share nothing with the SQL path:
no parser, no planner, other join and aggregation machinery.  Dates are
days since the epoch as ints, decimals become floats (the comparison
uses tolerances).  A query added to ``benchmark/queries/tpch/`` gets
its program in a new module beside this one, named in ``PROGRAMS``
there; ``make_expected.py`` collects every module's ``PROGRAMS``.
"""

from __future__ import annotations

import datetime

import pandas as pd

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def D(y: int, m: int, d: int) -> int:
    return datetime.date(y, m, d).toordinal() - _EPOCH


def _rows(df: "pd.DataFrame") -> list:
    return [tuple(r) for r in df.itertuples(index=False)]


def q01(F):
    li = F["lineitem"]
    li = li[li.l_shipdate <= D(1998, 12, 1) - 90].copy()
    li["disc_price"] = li.l_extendedprice * (1 - li.l_discount)
    li["charge"] = li.disc_price * (1 + li.l_tax)
    g = li.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"), sum_base=("l_extendedprice", "sum"),
        sum_disc=("disc_price", "sum"), sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"), avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"), n=("l_quantity", "size"))
    return _rows(g.sort_values(["l_returnflag", "l_linestatus"]))


def q03(F):
    c = F["customer"]; o = F["orders"]; li = F["lineitem"]
    c = c[c.c_mktsegment == "BUILDING"]
    o = o[o.o_orderdate < D(1995, 3, 15)]
    li = li[li.l_shipdate > D(1995, 3, 15)]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey").merge(
        c, left_on="o_custkey", right_on="c_custkey")
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).agg(revenue=("rev", "sum"))
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(10)
    return _rows(g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]])


def q06(F):
    li = F["lineitem"]
    m = ((li.l_shipdate >= D(1994, 1, 1)) & (li.l_shipdate < D(1995, 1, 1))
         & (li.l_discount >= 0.05 - 1e-9) & (li.l_discount <= 0.07 + 1e-9)
         & (li.l_quantity < 24))
    return [((li[m].l_extendedprice * li[m].l_discount).sum(),)]


def q14(F):
    li, p = F["lineitem"], F["part"]
    li = li[(li.l_shipdate >= D(1995, 9, 1)) & (li.l_shipdate < D(1995, 10, 1))]
    j = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    rev = j.l_extendedprice * (1 - j.l_discount)
    promo = rev[j.p_type.str.startswith("PROMO")].sum()
    return [(100.0 * promo / rev.sum(),)]


PROGRAMS = {"q01": q01, "q03": q03, "q06": q06, "q14": q14}
