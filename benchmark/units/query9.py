"""Plain reference of TPC-DS query 9 (query9.tpl beside this file)."""
from benchmark.refdata import FLOAT, Answer, ratio


def reference(wh, params):
    ss = wh.table("store_sales", ["ss_quantity", "ss_ext_discount_amt",
                                  "ss_net_paid"])
    reason = wh.table("reason", ["r_reason_sk"])
    n_out = int((reason.r_reason_sk == 1).fillna(False).sum())
    row = []
    for i, lo in enumerate((1, 21, 41, 61, 81), start=1):
        b = ss[ss.ss_quantity.between(lo, lo + 19).fillna(False)]
        col = b.ss_ext_discount_amt if len(b) > int(params[f"RC{i}"]) \
            else b.ss_net_paid
        row.append(ratio(col.sum(min_count=1), col.count(), 2))
    return Answer([f"bucket{i}" for i in range(1, 6)], [FLOAT] * 5,
                  [tuple(row)] * n_out)
