"""Plain reference of TPC-DS query 3 (query3.tpl beside this file)."""
from benchmark.refdata import DECIMAL, EXACT, Answer, cell, null_first


def reference(wh, params):
    manufact, month = int(params["MANUFACT"]), int(params["MONTH"])
    item = wh.table("item", ["i_item_sk", "i_brand_id", "i_brand",
                             "i_manufact_id"])
    item = item[(item.i_manufact_id == manufact).fillna(False)]
    dt = wh.table("date_dim", ["d_date_sk", "d_year", "d_moy"])
    dt = dt[(dt.d_moy == month).fillna(False)]
    ss = wh.table("store_sales", ["ss_sold_date_sk", "ss_item_sk",
                                  "ss_ext_sales_price"])
    j = ss.merge(item, left_on="ss_item_sk", right_on="i_item_sk") \
          .merge(dt, left_on="ss_sold_date_sk", right_on="d_date_sk")
    g = j.groupby(["d_year", "i_brand", "i_brand_id"], dropna=False) \
         .ss_ext_sales_price.sum(min_count=1).reset_index()
    rows = [(cell(r.d_year), cell(r.i_brand_id), cell(r.i_brand),
             None if cell(r.ss_ext_sales_price) is None
             else (int(r.ss_ext_sales_price), 2))
            for r in g.itertuples()]
    # ORDER BY d_year, sum_agg DESC (NULLs last), brand_id
    rows.sort(key=lambda r: (null_first(r[0]),
                             (1, 0) if r[3] is None else (0, -r[3][0]),
                             null_first(r[1])))
    return Answer(["d_year", "brand_id", "brand", "sum_agg"],
                  [EXACT, EXACT, EXACT, DECIMAL], rows, limit=100,
                  sort_cols=(0, 3, 1))
