"""Plain reference of TPC-DS query 22 (query22.tpl beside this file).

Pinned: ``ROLLUP`` over four keys emits the five prefixes; the answer has no
``GROUPING()`` column, so a subtotal row and the row of a real NULL key can
read alike and both stand. ``AVG(inv_quantity_on_hand)`` is over the non-NULL
quantities, NULL where there is none; ORDER BY it FIRST, NULLs first, on the
exact rational sum / count — the answer's cell is that rational rounded
once — then the four names, NULLs first."""
from fractions import Fraction

from benchmark.refdata import EXACT, FLOAT, Answer, null_first, ratio
from benchmark.units._strata import month_window, rollup

_KEYS = ["i_product_name", "i_brand", "i_class", "i_category"]


def reference(wh, params):
    days = month_window(wh, int(params["DMS"]))
    item = wh.table("item", ["i_item_sk"] + _KEYS)
    inv = wh.table("inventory", ["inv_date_sk", "inv_item_sk",
                                 "inv_quantity_on_hand"])
    j = inv.merge(days[["d_date_sk"]], left_on="inv_date_sk",
                  right_on="d_date_sk") \
           .merge(item, left_on="inv_item_sk", right_on="i_item_sk")
    keyed = []
    for key, _bits, s, n in rollup(j, _KEYS, "inv_quantity_on_hand"):
        exact = None if s is None else Fraction(s, n)
        keyed.append((tuple(null_first(k) for k in (exact,) + key),
                      key + (ratio(s, n),)))
    rows = [row for _order, row in sorted(keyed, key=lambda kr: kr[0])]
    return Answer(_KEYS + ["qoh"], [EXACT] * 4 + [FLOAT], rows, limit=100,
                  sort_cols=(4, 0, 1, 2, 3))
