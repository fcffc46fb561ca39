"""Plain reference of TPC-DS query 7 (query7.tpl beside this file)."""
from benchmark.refdata import EXACT, FLOAT, Answer, cell, null_first, ratio


def reference(wh, params):
    year = int(params["YEAR"])
    cd = wh.table("customer_demographics",
                  ["cd_demo_sk", "cd_gender", "cd_marital_status",
                   "cd_education_status"])
    cd = cd[((cd.cd_gender == params["GEN"])
             & (cd.cd_marital_status == params["MS"])
             & (cd.cd_education_status == params["ES"])).fillna(False)]
    promo = wh.table("promotion", ["p_promo_sk", "p_channel_email",
                                   "p_channel_event"])
    promo = promo[((promo.p_channel_email == "N")
                   | (promo.p_channel_event == "N")).fillna(False)]
    dt = wh.table("date_dim", ["d_date_sk", "d_year"])
    dt = dt[(dt.d_year == year).fillna(False)]
    item = wh.table("item", ["i_item_sk", "i_item_id"])
    vals = ["ss_quantity", "ss_list_price", "ss_coupon_amt",
            "ss_sales_price"]
    ss = wh.table("store_sales", ["ss_sold_date_sk", "ss_item_sk",
                                  "ss_cdemo_sk", "ss_promo_sk"] + vals)
    j = ss.merge(dt[["d_date_sk"]], left_on="ss_sold_date_sk",
                 right_on="d_date_sk") \
          .merge(cd[["cd_demo_sk"]], left_on="ss_cdemo_sk",
                 right_on="cd_demo_sk") \
          .merge(promo[["p_promo_sk"]], left_on="ss_promo_sk",
                 right_on="p_promo_sk") \
          .merge(item, left_on="ss_item_sk", right_on="i_item_sk")
    g = j.groupby("i_item_id", dropna=False)[vals]
    sums, counts = g.sum(min_count=1), g.count()
    rows = []
    for key in sums.index:
        rows.append((cell(key),) + tuple(
            ratio(sums.at[key, v], counts.at[key, v],
                  0 if v == "ss_quantity" else 2) for v in vals))
    rows.sort(key=lambda r: null_first(r[0]))
    return Answer(["i_item_id", "agg1", "agg2", "agg3", "agg4"],
                  [EXACT, FLOAT, FLOAT, FLOAT, FLOAT], rows, limit=100,
                  sort_cols=(0,))
