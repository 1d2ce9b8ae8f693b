"""Hand-written pandas programs for the queries of
``benchmark/queries/tpcds/``: TPC-DS Q3 and Q7 over the ``store_sales``
star, as ``ds_q03`` and ``ds_q07`` (``make_expected.programs`` merges
every module's ``PROGRAMS`` by name, and ``q03`` is TPC-H's).

Plain pandas over the frames ``make_expected.load_frames`` decodes from
the generator (strings decoded, decimals as floats): boolean masks on
the dimensions, ``merge`` onto the fact table, ``groupby``,
``sort_values``, ``head(100)``.  They share nothing with the SQL path
and import nothing of ``presto_tpu``.  Each dimension is filtered
before it is merged, which is the query's own meaning (every predicate
is on one table) and keeps the SF10 run at a few GiB.

Departures from the query text, each beside its line: money is summed
in whole cents (``load_frames`` hands decimals over as floats; the
data is in cents, so ``round(x * 100)`` is exact) so that ``ds_q03``'s
``ORDER BY sum_agg DESC`` cannot part from the engine's exact decimal
sums by a float's last bit; both ``ORDER BY`` lists are total orders
over their groups (``i_brand`` is a function of ``i_brand_id``,
``i_item_id`` of the item), so the 100 rows under the ``LIMIT`` are
determined and the comparison is ordered.
"""

from __future__ import annotations

import pandas as pd


def _rows(df: "pd.DataFrame") -> list:
    return [tuple(r) for r in df.itertuples(index=False)]


def _cents(money: "pd.Series") -> "pd.Series":
    return (money * 100).round().astype("int64")


def ds_q03(F):
    ss, item, dates = F["store_sales"], F["item"], F["date_dim"]
    item = item[item.i_manufact_id == 128]
    dates = dates[dates.d_moy == 11]
    j = ss[["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"]] \
        .merge(item, left_on="ss_item_sk", right_on="i_item_sk") \
        .merge(dates, left_on="ss_sold_date_sk", right_on="d_date_sk")
    j = j.assign(cents=_cents(j.ss_ext_sales_price))  # exact sums
    g = j.groupby(["d_year", "i_brand_id", "i_brand"], as_index=False).agg(
        cents=("cents", "sum"))
    g = g.sort_values(["d_year", "cents", "i_brand_id"],
                      ascending=[True, False, True]).head(100)
    g = g.assign(sum_agg=g.cents / 100.0)
    return _rows(g[["d_year", "i_brand_id", "i_brand", "sum_agg"]])


def ds_q07(F):
    ss, item = F["store_sales"], F["item"]
    cd, dates, promo = (F["customer_demographics"], F["date_dim"],
                        F["promotion"])
    cd = cd[(cd.cd_gender == "M") & (cd.cd_marital_status == "S")
            & (cd.cd_education_status == "College")]
    dates = dates[dates.d_year == 2000]
    promo = promo[(promo.p_channel_email == "N")
                  | (promo.p_channel_event == "N")]
    # ss_promo_sk is 0 on a fifth of the rows: a key that matches no
    # promotion (p_promo_sk starts at 1), not a NULL; the inner merge
    # drops those rows, as the query's join does
    j = ss.merge(cd[["cd_demo_sk"]], left_on="ss_cdemo_sk",
                 right_on="cd_demo_sk") \
        .merge(dates[["d_date_sk"]], left_on="ss_sold_date_sk",
               right_on="d_date_sk") \
        .merge(promo[["p_promo_sk"]], left_on="ss_promo_sk",
               right_on="p_promo_sk") \
        .merge(item[["i_item_sk", "i_item_id"]], left_on="ss_item_sk",
               right_on="i_item_sk")
    money = {c: _cents(j[c]) for c in ("ss_list_price", "ss_coupon_amt",
                                       "ss_sales_price")}
    g = j.assign(**money).groupby("i_item_id", as_index=False).agg(
        n=("ss_quantity", "size"), quantity=("ss_quantity", "sum"),
        list_price=("ss_list_price", "sum"),
        coupon_amt=("ss_coupon_amt", "sum"),
        sales_price=("ss_sales_price", "sum"))
    g = g.sort_values("i_item_id").head(100)
    # avg = exact sum / count, in float; the engine rounds an
    # avg(decimal(12,2)) HALF_UP at scale 2 and rows_match allows it
    # half a unit there
    return _rows(pd.DataFrame({
        "i_item_id": g.i_item_id,
        "agg1": g.quantity / g.n,
        "agg2": g.list_price / 100.0 / g.n,
        "agg3": g.coupon_amt / 100.0 / g.n,
        "agg4": g.sales_price / 100.0 / g.n}))


PROGRAMS = {"ds_q03": ds_q03, "ds_q07": ds_q07}
