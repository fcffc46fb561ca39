"""Plain reference of TPC-DS query 47 (query47.tpl beside this file).

Pinned: what ``_strata.monthly_outliers`` states — the windows of ``v1`` run
over its GROUP BY's rows, the neighbours at ``rn - 1`` / ``rn + 1`` are by
rank, a NULL category, brand, store or company name joins nothing, and the
ratio filter and the first ORDER BY key are decided exactly."""
from benchmark.units._strata import monthly_outliers


def reference(wh, params):
    return monthly_outliers(
        wh, int(params["YEAR"]),
        ("store_sales", "ss_sold_date_sk", "ss_item_sk", "ss_sales_price"),
        ("store", "s_store_sk", "ss_store_sk",
         ["s_store_name", "s_company_name"]))
