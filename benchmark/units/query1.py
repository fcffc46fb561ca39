"""Plain reference of TPC-DS query 1 (query1.tpl beside this file)."""
from benchmark.refdata import EXACT, Answer, cell, null_first


def reference(wh, params):
    year = int(params["YEAR"])
    dt = wh.table("date_dim", ["d_date_sk", "d_year"])
    dt = dt[(dt.d_year == year).fillna(False)]
    sr = wh.table("store_returns", ["sr_returned_date_sk", "sr_customer_sk",
                                    "sr_store_sk", "sr_return_amt"])
    sr = sr.merge(dt[["d_date_sk"]], left_on="sr_returned_date_sk",
                  right_on="d_date_sk")
    ctr = sr.groupby(["sr_customer_sk", "sr_store_sk"], dropna=False) \
            .sr_return_amt.sum(min_count=1).reset_index(name="total")
    # AVG over the store's non-NULL totals; a NULL store matches no one
    per_store = ctr.dropna(subset=["sr_store_sk", "total"]) \
                   .groupby("sr_store_sk").total.agg(["sum", "count"])
    ctr = ctr.dropna(subset=["total"]).merge(
        per_store, left_on="sr_store_sk", right_index=True)
    # total > avg * 1.2  <=>  5 * total * count > 6 * sum, in integers
    keep = [5 * int(t) * int(n) > 6 * int(s)
            for t, n, s in zip(ctr.total, ctr["count"], ctr["sum"])]
    ctr = ctr[keep]
    store = wh.table("store", ["s_store_sk", "s_state"])
    store = store[(store.s_state == params["STATE"]).fillna(False)]
    cust = wh.table("customer", ["c_customer_sk", "c_customer_id"])
    j = ctr.merge(store, left_on="sr_store_sk", right_on="s_store_sk") \
           .merge(cust, left_on="sr_customer_sk", right_on="c_customer_sk")
    rows = sorted(((cell(v),) for v in j.c_customer_id),
                  key=lambda r: null_first(r[0]))
    return Answer(["c_customer_id"], [EXACT], rows, limit=100,
                  sort_cols=(0,))
