"""Plain reference of TPC-DS query 93 (query93.tpl beside this file).

Pinned: the LEFT OUTER JOIN keeps a sale that finds no return with NULL
``sr_*`` columns, and the WHERE clause above it (``sr_reason_sk =
r_reason_sk``) then rejects that row: NULL equals nothing. The CASE is
NULL-aware — a return whose quantity is NULL counts the whole sale — and the
product is NULL where ``ss_quantity`` or ``ss_sales_price`` is. A NULL
``ss_customer_sk`` is a group; ORDER BY the exact sum (NULLs first), then
the customer."""
from benchmark.refdata import DECIMAL, EXACT, Answer, null_first
from benchmark.units._strata import dec, group_sums


def reference(wh, params):
    rid = int(params["RID"])
    reason = wh.table("reason", ["r_reason_sk"])
    reason = reason[(reason.r_reason_sk == rid).fillna(False)]
    ss = wh.table("store_sales", ["ss_item_sk", "ss_ticket_number",
                                  "ss_customer_sk", "ss_quantity",
                                  "ss_sales_price"])
    sr = wh.table("store_returns", ["sr_item_sk", "sr_ticket_number",
                                    "sr_reason_sk", "sr_return_quantity"])
    j = ss.merge(sr, how="left", left_on=["ss_item_sk", "ss_ticket_number"],
                 right_on=["sr_item_sk", "sr_ticket_number"]) \
          .merge(reason, left_on="sr_reason_sk", right_on="r_reason_sk")
    kept = j.ss_quantity - j.sr_return_quantity.fillna(0)
    j = j.assign(act_sales=kept * j.ss_sales_price)
    rows = [(key[0], dec(s))
            for key, s, _n in group_sums(j, ["ss_customer_sk"], "act_sales")]
    rows.sort(key=lambda r: (null_first(None if r[1] is None else r[1][0]),
                             null_first(r[0])))
    return Answer(["ss_customer_sk", "sumsales"], [EXACT, DECIMAL], rows,
                  limit=100, sort_cols=(1, 0))
