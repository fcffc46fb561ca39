"""Plain reference of TPC-DS query 38 (query38.tpl beside this file).

Pinned: DISTINCT and INTERSECT are set operations, in which NULL equals
NULL — a customer with a NULL last or first name on every side is ONE
member of the intersection, where a join on the names would drop it. A
sale with a NULL customer or date key joins nothing."""
from benchmark.refdata import EXACT, Answer, cell
from benchmark.units._strata import month_window


def reference(wh, params):
    days = month_window(wh, int(params["DMS"]), ("d_date",))
    cust = wh.table("customer", ["c_customer_sk", "c_last_name",
                                 "c_first_name"])

    def buyers(table, date_col, cust_col):
        f = wh.table(table, [date_col, cust_col])
        j = f.merge(days[["d_date_sk", "d_date"]], left_on=date_col,
                    right_on="d_date_sk") \
             .merge(cust, left_on=cust_col, right_on="c_customer_sk")
        return {(cell(a), cell(b), d) for a, b, d in
                zip(j.c_last_name, j.c_first_name, j.d_date)}

    hot = buyers("store_sales", "ss_sold_date_sk", "ss_customer_sk") \
        & buyers("catalog_sales", "cs_sold_date_sk", "cs_bill_customer_sk") \
        & buyers("web_sales", "ws_sold_date_sk", "ws_bill_customer_sk")
    return Answer(["cnt"], [EXACT], [(len(hot),)], limit=100)
