"""Plain reference of TPC-DS query 57 (query57.tpl beside this file):
query47's statement over ``catalog_sales`` and ``call_center``.

Pinned: what ``_strata.monthly_outliers`` states — the windows of ``v1`` run
over its GROUP BY's rows, the neighbours at ``rn - 1`` / ``rn + 1`` are by
rank, a sale with a NULL call center and a NULL category, brand or call
center name join nothing, and the ratio filter and the first ORDER BY key
are decided exactly."""
from benchmark.units._strata import monthly_outliers


def reference(wh, params):
    return monthly_outliers(
        wh, int(params["YEAR"]),
        ("catalog_sales", "cs_sold_date_sk", "cs_item_sk", "cs_sales_price"),
        ("call_center", "cc_call_center_sk", "cs_call_center_sk",
         ["cc_name"]))
