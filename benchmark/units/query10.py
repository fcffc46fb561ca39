"""Plain reference of TPC-DS query 10 (query10.tpl beside this file)."""
from benchmark.refdata import EXACT, Answer, cell, null_first

_KEYS = ["cd_gender", "cd_marital_status", "cd_education_status",
         "cd_purchase_estimate", "cd_credit_rating", "cd_dep_count",
         "cd_dep_employed_count", "cd_dep_college_count"]


def reference(wh, params):
    year, month = int(params["YEAR"]), int(params["MONTH"])
    counties = [c.strip().strip("'") for c in params["COUNTIES"].split(",")]
    dt = wh.table("date_dim", ["d_date_sk", "d_year", "d_moy"])
    days = dt[((dt.d_year == year)
               & dt.d_moy.between(month, month + 3)).fillna(False)].d_date_sk

    def buyers(table, date_col, cust_col):
        f = wh.table(table, [date_col, cust_col])
        return set(f[f[date_col].isin(days)][cust_col].dropna())

    store = buyers("store_sales", "ss_sold_date_sk", "ss_customer_sk")
    other = buyers("web_sales", "ws_sold_date_sk", "ws_bill_customer_sk") \
        | buyers("catalog_sales", "cs_sold_date_sk", "cs_ship_customer_sk")
    c = wh.table("customer", ["c_customer_sk", "c_current_addr_sk",
                              "c_current_cdemo_sk"])
    c = c[c.c_customer_sk.isin(store & other)]
    ca = wh.table("customer_address", ["ca_address_sk", "ca_county"])
    ca = ca[ca.ca_county.isin(counties).fillna(False)]
    cd = wh.table("customer_demographics", ["cd_demo_sk"] + _KEYS)
    j = c.merge(ca, left_on="c_current_addr_sk", right_on="ca_address_sk") \
         .merge(cd, left_on="c_current_cdemo_sk", right_on="cd_demo_sk")
    g = j.groupby(_KEYS, dropna=False).size().reset_index(name="cnt")
    rows = []
    for r in g.itertuples(index=False):
        k = [cell(v) for v in r[:8]]
        n = int(r.cnt)
        rows.append((k[0], k[1], k[2], n, k[3], n, k[4], n, k[5], n,
                     k[6], n, k[7], n))
    order = (0, 1, 2, 4, 6, 8, 10, 12)
    rows.sort(key=lambda r: tuple(null_first(r[i]) for i in order))
    names = ["cd_gender", "cd_marital_status", "cd_education_status", "cnt1",
             "cd_purchase_estimate", "cnt2", "cd_credit_rating", "cnt3",
             "cd_dep_count", "cnt4", "cd_dep_employed_count", "cnt5",
             "cd_dep_college_count", "cnt6"]
    return Answer(names, [EXACT] * 14, rows, limit=100, sort_cols=order)
