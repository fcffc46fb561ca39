"""Plain reference of TPC-DS query 20 (query20.tpl beside this file).

Pinned: the window ``SUM(SUM(x)) OVER (PARTITION BY i_class)`` runs over the
GROUP BY's rows, a NULL class is a partition of its own, and a class whose
revenue sums to 0 or NULL gives a NULL ratio. ``revenueratio`` is the exact
rational 100 * item / class, rounded once; it is the LAST order key, and the
order is decided on the rational."""
import datetime
from fractions import Fraction

from benchmark.refdata import DECIMAL, EXACT, FLOAT, Answer, null_first, ratio
from benchmark.units._strata import dec, group_sums

_KEYS = ["i_item_id", "i_item_desc", "i_category", "i_class",
         "i_current_price"]


def reference(wh, params):
    start = datetime.date(int(params["YEAR"]), int(params["MONTH"]),
                          int(params["DAY"]))
    end = start + datetime.timedelta(days=30)
    cats = [c.strip().strip("'") for c in params["CATS"].split(",")]
    dt = wh.table("date_dim", ["d_date_sk", "d_date"])
    dt = dt[[d is not None and start <= d <= end for d in dt.d_date]]
    item = wh.table("item", ["i_item_sk"] + _KEYS)
    item = item[item.i_category.isin(cats).fillna(False)]
    cs = wh.table("catalog_sales", ["cs_sold_date_sk", "cs_item_sk",
                                    "cs_ext_sales_price"])
    j = cs.merge(dt[["d_date_sk"]], left_on="cs_sold_date_sk",
                 right_on="d_date_sk") \
          .merge(item, left_on="cs_item_sk", right_on="i_item_sk")
    groups = group_sums(j, _KEYS, "cs_ext_sales_price")
    by_class: dict = {}
    for key, rev, _n in groups:
        if rev is not None:
            by_class[key[3]] = by_class.get(key[3], 0) + rev
    keyed = []
    for key, rev, _n in groups:
        total = by_class.get(key[3])
        row = key[:4] + (dec(key[4]), dec(rev),
                         None if rev is None else ratio(rev * 100, total))
        exact = None if row[6] is None else Fraction(rev * 100, total)
        keyed.append((tuple(null_first(k) for k in (
            key[2], key[3], key[0], key[1], exact)), row))
    rows = [row for _order, row in sorted(keyed, key=lambda kr: kr[0])]
    return Answer(_KEYS + ["itemrevenue", "revenueratio"],
                  [EXACT] * 4 + [DECIMAL, DECIMAL, FLOAT], rows, limit=100,
                  sort_cols=(2, 3, 0, 1, 6))
