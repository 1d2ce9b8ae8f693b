"""Second independent TPC-H oracle: hand-written pandas programs.

VERDICT r2 #7 — correctness previously rested on ONE external engine
(sqlite) fed the same translated SQL text; a systematic bug in the
translation layer would go unnoticed.  These dataframe programs share
NOTHING with the SQL path (no parser, no translate(), different join /
aggregation machinery), so engine==sqlite==pandas triple agreement is
the presto-verifier-style cross-engine bar the environment allows
(DuckDB is not installed).

Dates are days-since-epoch ints end to end; decimals become floats
(comparison uses tolerances).  Reference analog:
presto-verifier/.../Validator.java + H2QueryRunner as the second
engine.
"""

from __future__ import annotations

import datetime

import numpy as np
import pandas as pd

_EPOCH = datetime.date(1970, 1, 1).toordinal()


def D(y: int, m: int, d: int) -> int:
    return datetime.date(y, m, d).toordinal() - _EPOCH


def year_of(days: "pd.Series") -> "pd.Series":
    return pd.to_datetime(days, unit="D").dt.year


def load_frames(conn, columns=None) -> dict:
    """Decode the generator's columns into DataFrames (strings decoded,
    decimals scaled to float, dates as int days).  ``columns`` maps
    table -> column names and restricts the frames to those (at scale,
    decoding every comment column of every table is most of the cost);
    None decodes everything."""
    frames = {}
    for table in (conn.table_names() if columns is None else columns):
        schema = [(name, t) for name, t in conn.schema(table)
                  if columns is None or name in columns[table]]
        parts = []
        for split in range(conn.num_splits(table)):
            data = conn.generate_split(table, split)
            cols = {}
            for name, t in schema:
                arr = data[name]
                if t.is_string:
                    cols[name] = conn.dictionary_for(table, name).decode(arr)
                elif t.is_decimal:
                    cols[name] = arr / (10.0 ** t.scale)
                else:
                    cols[name] = arr
            parts.append(pd.DataFrame(cols))
        frames[table] = pd.concat(parts, ignore_index=True)
    return frames


def _rows(df: "pd.DataFrame") -> list:
    return [tuple(r) for r in df.itertuples(index=False)]


def q1(F):
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


def q2(F):
    p, s, ps, n, r = (F["part"], F["supplier"], F["partsupp"], F["nation"],
                      F["region"])
    p = p[(p.p_size == 15) & p.p_type.str.endswith("BRASS")]
    eu = n.merge(r[r.r_name == "EUROPE"], left_on="n_regionkey",
                 right_on="r_regionkey")
    se = s.merge(eu, left_on="s_nationkey", right_on="n_nationkey")
    j = ps.merge(p, left_on="ps_partkey", right_on="p_partkey").merge(
        se, left_on="ps_suppkey", right_on="s_suppkey")
    mins = j.groupby("p_partkey")["ps_supplycost"].transform("min")
    j = j[j.ps_supplycost == mins]
    j = j.sort_values(["s_acctbal", "n_name", "s_name", "p_partkey"],
                      ascending=[False, True, True, True]).head(100)
    return _rows(j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
                    "s_address", "s_phone", "s_comment"]])


def q3(F):
    c = F["customer"]; o = F["orders"]; li = F["lineitem"]
    c = c[c.c_mktsegment == "BUILDING"]
    o = o[o.o_orderdate < D(1995, 3, 15)]
    li = li[li.l_shipdate > D(1995, 3, 15)].copy()
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey").merge(
        c, left_on="o_custkey", right_on="c_custkey")
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).agg(revenue=("rev", "sum"))
    g = g.sort_values(["revenue", "o_orderdate"],
                      ascending=[False, True]).head(10)
    return _rows(g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]])


def q4(F):
    o = F["orders"]; li = F["lineitem"]
    o = o[(o.o_orderdate >= D(1993, 7, 1)) & (o.o_orderdate < D(1993, 10, 1))]
    keys = set(li[li.l_commitdate < li.l_receiptdate].l_orderkey)
    o = o[o.o_orderkey.isin(keys)]
    g = o.groupby("o_orderpriority", as_index=False).agg(
        n=("o_orderkey", "size"))
    return _rows(g.sort_values("o_orderpriority"))


def q5(F):
    c, o, li, s, n, r = (F["customer"], F["orders"], F["lineitem"],
                         F["supplier"], F["nation"], F["region"])
    o = o[(o.o_orderdate >= D(1994, 1, 1)) & (o.o_orderdate < D(1995, 1, 1))]
    asia = n.merge(r[r.r_name == "ASIA"], left_on="n_regionkey",
                   right_on="r_regionkey")
    j = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
           .merge(c, left_on="o_custkey", right_on="c_custkey")
           .merge(s, left_on="l_suppkey", right_on="s_suppkey"))
    j = j[j.c_nationkey == j.s_nationkey]
    j = j.merge(asia, left_on="s_nationkey", right_on="n_nationkey")
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby("n_name", as_index=False).agg(revenue=("rev", "sum"))
    return _rows(g.sort_values("revenue", ascending=False))


def q6(F):
    li = F["lineitem"]
    m = ((li.l_shipdate >= D(1994, 1, 1)) & (li.l_shipdate < D(1995, 1, 1))
         & (li.l_discount >= 0.05 - 1e-9) & (li.l_discount <= 0.07 + 1e-9)
         & (li.l_quantity < 24))
    return [( (li[m].l_extendedprice * li[m].l_discount).sum(), )]


def q7(F):
    s, li, o, c, n = (F["supplier"], F["lineitem"], F["orders"],
                      F["customer"], F["nation"])
    li = li[(li.l_shipdate >= D(1995, 1, 1)) & (li.l_shipdate <= D(1996, 12, 31))]
    j = (li.merge(s, left_on="l_suppkey", right_on="s_suppkey")
           .merge(o, left_on="l_orderkey", right_on="o_orderkey")
           .merge(c, left_on="o_custkey", right_on="c_custkey")
           .merge(n.rename(columns=lambda x: x + "_1"),
                  left_on="s_nationkey", right_on="n_nationkey_1")
           .merge(n.rename(columns=lambda x: x + "_2"),
                  left_on="c_nationkey", right_on="n_nationkey_2"))
    m = (((j.n_name_1 == "FRANCE") & (j.n_name_2 == "GERMANY"))
         | ((j.n_name_1 == "GERMANY") & (j.n_name_2 == "FRANCE")))
    j = j[m].copy()
    j["l_year"] = year_of(j.l_shipdate)
    j["vol"] = j.l_extendedprice * (1 - j.l_discount)
    g = j.groupby(["n_name_1", "n_name_2", "l_year"], as_index=False).agg(
        revenue=("vol", "sum"))
    return _rows(g.sort_values(["n_name_1", "n_name_2", "l_year"]))


def q8(F):
    p, s, li, o, c, n, r = (F["part"], F["supplier"], F["lineitem"],
                            F["orders"], F["customer"], F["nation"],
                            F["region"])
    p = p[p.p_type == "ECONOMY ANODIZED STEEL"]
    o = o[(o.o_orderdate >= D(1995, 1, 1)) & (o.o_orderdate <= D(1996, 12, 31))]
    am = n.merge(r[r.r_name == "AMERICA"], left_on="n_regionkey",
                 right_on="r_regionkey")
    j = (li.merge(p, left_on="l_partkey", right_on="p_partkey")
           .merge(s, left_on="l_suppkey", right_on="s_suppkey")
           .merge(o, left_on="l_orderkey", right_on="o_orderkey")
           .merge(c, left_on="o_custkey", right_on="c_custkey")
           .merge(am[["n_nationkey"]], left_on="c_nationkey",
                  right_on="n_nationkey")
           .merge(n[["n_nationkey", "n_name"]].rename(
               columns={"n_nationkey": "sk", "n_name": "nation"}),
               left_on="s_nationkey", right_on="sk"))
    j = j.assign(o_year=year_of(j.o_orderdate),
                 vol=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby("o_year").apply(
        lambda t: t.loc[t.nation == "BRAZIL", "vol"].sum() / t.vol.sum(),
        include_groups=False).reset_index()
    return _rows(g.sort_values("o_year"))


def q9(F):
    p, s, li, ps, o, n = (F["part"], F["supplier"], F["lineitem"],
                          F["partsupp"], F["orders"], F["nation"])
    p = p[p.p_name.str.contains("green")]
    j = (li.merge(p[["p_partkey"]], left_on="l_partkey", right_on="p_partkey")
           .merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey",
                  right_on="s_suppkey")
           .merge(ps[["ps_partkey", "ps_suppkey", "ps_supplycost"]],
                  left_on=["l_partkey", "l_suppkey"],
                  right_on=["ps_partkey", "ps_suppkey"])
           .merge(o[["o_orderkey", "o_orderdate"]], left_on="l_orderkey",
                  right_on="o_orderkey")
           .merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey",
                  right_on="n_nationkey"))
    j = j.assign(o_year=year_of(j.o_orderdate),
                 amount=j.l_extendedprice * (1 - j.l_discount)
                 - j.ps_supplycost * j.l_quantity)
    g = j.groupby(["n_name", "o_year"], as_index=False).agg(
        profit=("amount", "sum"))
    return _rows(g.sort_values(["n_name", "o_year"],
                               ascending=[True, False]))


def q10(F):
    c, o, li, n = F["customer"], F["orders"], F["lineitem"], F["nation"]
    o = o[(o.o_orderdate >= D(1993, 10, 1)) & (o.o_orderdate < D(1994, 1, 1))]
    li = li[li.l_returnflag == "R"]
    j = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
           .merge(c, left_on="o_custkey", right_on="c_custkey")
           .merge(n, left_on="c_nationkey", right_on="n_nationkey"))
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
                   "c_address", "c_comment"], as_index=False).agg(
        revenue=("rev", "sum"))
    g = g.sort_values("revenue", ascending=False).head(20)
    return _rows(g[["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
                    "c_address", "c_phone", "c_comment"]])


def q11(F):
    ps, s, n = F["partsupp"], F["supplier"], F["nation"]
    de = s.merge(n[n.n_name == "GERMANY"], left_on="s_nationkey",
                 right_on="n_nationkey")
    j = ps.merge(de[["s_suppkey"]], left_on="ps_suppkey", right_on="s_suppkey")
    j = j.assign(v=j.ps_supplycost * j.ps_availqty)
    g = j.groupby("ps_partkey", as_index=False).agg(value=("v", "sum"))
    g = g[g.value > j.v.sum() * 0.0001]
    return _rows(g.sort_values("value", ascending=False))


def q12(F):
    o, li = F["orders"], F["lineitem"]
    li = li[li.l_shipmode.isin(["MAIL", "SHIP"])
            & (li.l_commitdate < li.l_receiptdate)
            & (li.l_shipdate < li.l_commitdate)
            & (li.l_receiptdate >= D(1994, 1, 1))
            & (li.l_receiptdate < D(1995, 1, 1))]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    hi = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    g = j.assign(hi=hi.astype(int), lo=(~hi).astype(int)).groupby(
        "l_shipmode", as_index=False).agg(high=("hi", "sum"), low=("lo", "sum"))
    return _rows(g.sort_values("l_shipmode"))


def q13(F):
    c, o = F["customer"], F["orders"]
    o = o[~o.o_comment.str.contains(r"special.*requests", regex=True)]
    cnt = o.groupby("o_custkey").size()
    c_count = c.c_custkey.map(cnt).fillna(0).astype(int)
    g = c_count.value_counts().reset_index()
    g.columns = ["c_count", "custdist"]
    return _rows(g.sort_values(["custdist", "c_count"],
                               ascending=[False, False]))


def q14(F):
    li, p = F["lineitem"], F["part"]
    li = li[(li.l_shipdate >= D(1995, 9, 1)) & (li.l_shipdate < D(1995, 10, 1))]
    j = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    rev = j.l_extendedprice * (1 - j.l_discount)
    promo = rev[j.p_type.str.startswith("PROMO")].sum()
    return [(100.0 * promo / rev.sum(),)]


def q15(F):
    s, li = F["supplier"], F["lineitem"]
    li = li[(li.l_shipdate >= D(1996, 1, 1)) & (li.l_shipdate < D(1996, 4, 1))]
    li = li.assign(rev=li.l_extendedprice * (1 - li.l_discount))
    g = li.groupby("l_suppkey", as_index=False).agg(total=("rev", "sum"))
    g = g[np.isclose(g.total, g.total.max(), rtol=0, atol=1e-9)]
    j = g.merge(s, left_on="l_suppkey", right_on="s_suppkey")
    j = j.sort_values("s_suppkey")
    return _rows(j[["s_suppkey", "s_name", "s_address", "s_phone", "total"]])


def q16(F):
    ps, p, s = F["partsupp"], F["part"], F["supplier"]
    p = p[(p.p_brand != "Brand#45")
          & ~p.p_type.str.startswith("MEDIUM POLISHED")
          & p.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])]
    bad = set(s[s.s_comment.str.contains(r"Customer.*Complaints",
                                         regex=True)].s_suppkey)
    j = ps.merge(p, left_on="ps_partkey", right_on="p_partkey")
    j = j[~j.ps_suppkey.isin(bad)]
    g = j.groupby(["p_brand", "p_type", "p_size"], as_index=False).agg(
        cnt=("ps_suppkey", "nunique"))
    g = g.sort_values(["cnt", "p_brand", "p_type", "p_size"],
                      ascending=[False, True, True, True])
    return _rows(g[["p_brand", "p_type", "p_size", "cnt"]])


def q17(F):
    li, p = F["lineitem"], F["part"]
    p = p[(p.p_brand == "Brand#23") & (p.p_container == "MED BOX")]
    avg_q = li.groupby("l_partkey")["l_quantity"].mean()
    j = li.merge(p[["p_partkey"]], left_on="l_partkey", right_on="p_partkey")
    j = j[j.l_quantity < 0.2 * j.l_partkey.map(avg_q)]
    return [(j.l_extendedprice.sum() / 7.0,)]


def q18(F):
    c, o, li = F["customer"], F["orders"], F["lineitem"]
    big = li.groupby("l_orderkey")["l_quantity"].sum()
    keys = set(big[big > 300].index)
    o = o[o.o_orderkey.isin(keys)]
    j = (li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
           .merge(c, left_on="o_custkey", right_on="c_custkey"))
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False).agg(q=("l_quantity", "sum"))
    g = g.sort_values(["o_totalprice", "o_orderdate"],
                      ascending=[False, True]).head(100)
    return _rows(g)


def q19(F):
    li, p = F["lineitem"], F["part"]
    j = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    common = (j.l_shipmode.isin(["AIR", "AIR REG"])
              & (j.l_shipinstruct == "DELIVER IN PERSON"))
    b1 = ((j.p_brand == "Brand#12")
          & j.p_container.isin(["SM CASE", "SM BOX", "SM PACK", "SM PKG"])
          & (j.l_quantity >= 1) & (j.l_quantity <= 11)
          & (j.p_size >= 1) & (j.p_size <= 5))
    b2 = ((j.p_brand == "Brand#23")
          & j.p_container.isin(["MED BAG", "MED BOX", "MED PKG", "MED PACK"])
          & (j.l_quantity >= 10) & (j.l_quantity <= 20)
          & (j.p_size >= 1) & (j.p_size <= 10))
    b3 = ((j.p_brand == "Brand#34")
          & j.p_container.isin(["LG CASE", "LG BOX", "LG PACK", "LG PKG"])
          & (j.l_quantity >= 20) & (j.l_quantity <= 30)
          & (j.p_size >= 1) & (j.p_size <= 15))
    m = common & (b1 | b2 | b3)
    return [((j[m].l_extendedprice * (1 - j[m].l_discount)).sum(),)]


def q20(F):
    s, n, ps, p, li = (F["supplier"], F["nation"], F["partsupp"], F["part"],
                       F["lineitem"])
    forest = set(p[p.p_name.str.startswith("forest")].p_partkey)
    li = li[(li.l_shipdate >= D(1994, 1, 1)) & (li.l_shipdate < D(1995, 1, 1))]
    sold = li.groupby(["l_partkey", "l_suppkey"], as_index=False).agg(
        sold=("l_quantity", "sum"))
    psf = ps[ps.ps_partkey.isin(forest)].merge(
        sold, how="left", left_on=["ps_partkey", "ps_suppkey"],
        right_on=["l_partkey", "l_suppkey"])
    # SQL semantics: the correlated sum over zero lineitems is NULL,
    # and availqty > NULL is false — unmatched rows never qualify
    good = set(psf[psf.ps_availqty > 0.5 * psf.sold].ps_suppkey)
    j = s[s.s_suppkey.isin(good)].merge(
        n[n.n_name == "CANADA"], left_on="s_nationkey", right_on="n_nationkey")
    return _rows(j.sort_values("s_name")[["s_name", "s_address"]])


def q21(F):
    s, li, o, n = F["supplier"], F["lineitem"], F["orders"], F["nation"]
    late = li[li.l_receiptdate > li.l_commitdate]
    supp_per_order = li.groupby("l_orderkey")["l_suppkey"].nunique()
    late_supp_per_order = late.groupby("l_orderkey")["l_suppkey"].nunique()
    j = (late.merge(o[o.o_orderstatus == "F"], left_on="l_orderkey",
                    right_on="o_orderkey")
             .merge(s, left_on="l_suppkey", right_on="s_suppkey")
             .merge(n[n.n_name == "SAUDI ARABIA"], left_on="s_nationkey",
                    right_on="n_nationkey"))
    multi = j.l_orderkey.map(supp_per_order) > 1
    only_late = j.l_orderkey.map(late_supp_per_order) == 1
    j = j[multi & only_late]
    g = j.groupby("s_name", as_index=False).agg(numwait=("l_orderkey", "size"))
    g = g.sort_values(["numwait", "s_name"], ascending=[False, True]).head(100)
    return _rows(g)


def q22(F):
    c, o = F["customer"], F["orders"]
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cc = c[c.c_phone.str[:2].isin(codes)]
    avg_bal = cc[cc.c_acctbal > 0.0].c_acctbal.mean()
    with_orders = set(o.o_custkey)
    sel = cc[(cc.c_acctbal > avg_bal) & ~cc.c_custkey.isin(with_orders)]
    g = sel.assign(code=sel.c_phone.str[:2]).groupby("code", as_index=False).agg(
        numcust=("c_acctbal", "size"), total=("c_acctbal", "sum"))
    return _rows(g.sort_values("code"))


PANDAS_QUERIES = {i: globals()[f"q{i}"] for i in range(1, 23)}
