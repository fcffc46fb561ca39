"""Plain reference of TPC-DS query 76 (query76.tpl beside this file).

Pinned: UNION ALL keeps every row of the three channels; ``<column> IS
NULL`` is true for a NULL foreign key only, and such a sale still joins
``item`` and ``date_dim`` on its other keys (a NULL date key joins nothing).
``COUNT(*)`` counts rows, ``SUM`` skips NULL prices and is NULL where none is
left; a NULL category is a group, first in ascending order."""
from benchmark.refdata import DECIMAL, EXACT, Answer, cell, null_first
from benchmark.units._strata import dec

_KEYS = ["d_year", "d_qoy", "i_category"]


def reference(wh, params):
    dt = wh.table("date_dim", ["d_date_sk", "d_year", "d_qoy"])
    item = wh.table("item", ["i_item_sk", "i_category"])
    rows = []
    for channel, table, pre, param in (
            ("store", "store_sales", "ss", "NULLCOLSS"),
            ("web", "web_sales", "ws", "NULLCOLWS"),
            ("catalog", "catalog_sales", "cs", "NULLCOLCS")):
        col, price = params[param], f"{pre}_ext_sales_price"
        f = wh.table(table, [col, f"{pre}_sold_date_sk", f"{pre}_item_sk",
                             price])
        j = f[f[col].isna()] \
            .merge(dt, left_on=f"{pre}_sold_date_sk", right_on="d_date_sk") \
            .merge(item, left_on=f"{pre}_item_sk", right_on="i_item_sk")
        g = j.groupby(_KEYS, dropna=False)[price]
        sizes = g.size()
        for key, n, s in zip(sizes.index, sizes, g.sum(min_count=1)):
            rows.append((channel, col) + tuple(cell(k) for k in key)
                        + (int(n), dec(s)))
    rows.sort(key=lambda r: r[:2] + tuple(null_first(k) for k in r[2:5]))
    return Answer(["channel", "col_name"] + _KEYS + ["sales_cnt",
                                                     "sales_amt"],
                  [EXACT] * 6 + [DECIMAL], rows, limit=100,
                  sort_cols=(0, 1, 2, 3, 4))
