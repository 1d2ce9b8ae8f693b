"""Hand-written pandas programs for the queries of
``benchmark/queries/tpch_fkjoin/``: TPC-H Q4 and Q13.

Taken from ``tests/pandas_oracle.py`` (``q4``, ``q13``; the originals
stay for the repo's own tests).  They share nothing with the SQL path:
Q4's EXISTS is an ``isin`` over the order keys of the qualifying line
items, Q13's left outer join a ``groupby`` count mapped onto all
customers with 0 for those without a qualifying order.  Dates are days
since the epoch as ints.  Both answers are counts, so the comparison is
exact.
"""

from __future__ import annotations

import datetime

import pandas as pd

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def D(y: int, m: int, d: int) -> int:
    return datetime.date(y, m, d).toordinal() - _EPOCH


def _rows(df: "pd.DataFrame") -> list:
    return [tuple(r) for r in df.itertuples(index=False)]


def q04(F):
    o, li = F["orders"], F["lineitem"]
    o = o[(o.o_orderdate >= D(1993, 7, 1)) & (o.o_orderdate < D(1993, 10, 1))]
    late = li[li.l_commitdate < li.l_receiptdate].l_orderkey.unique()
    o = o[o.o_orderkey.isin(late)]
    g = o.groupby("o_orderpriority", as_index=False).agg(
        n=("o_orderkey", "size"))
    return _rows(g.sort_values("o_orderpriority"))


def q13(F):
    c, o = F["customer"], F["orders"]
    o = o[~o.o_comment.str.contains(r"special.*requests", regex=True)]
    per_customer = o.groupby("o_custkey").size()
    c_count = c.c_custkey.map(per_customer).fillna(0).astype(int)
    g = c_count.value_counts().reset_index()
    g.columns = ["c_count", "custdist"]
    return _rows(g.sort_values(["custdist", "c_count"],
                               ascending=[False, False]))


PROGRAMS = {"q04": q04, "q13": q13}
