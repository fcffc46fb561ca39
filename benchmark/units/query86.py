"""Plain reference of TPC-DS query 86 (query86.tpl beside this file).

Pinned: ``ROLLUP(i_category, i_class)`` emits (category, class), (category)
and () rows; a rolled-up key reads NULL with its ``GROUPING()`` bit 1 while
an item's real NULL category or class keeps the bit 0, so both kinds of row
stand side by side. ``RANK()`` is per (lochierarchy, the category where the
class is not rolled up), by the exact sum ``DESC`` with NULL sums last, ties
sharing a rank (1, 1, 3). ORDER BY: lochierarchy DESC, then the category of
the leaf rows only (NULLs first), then the rank."""
from benchmark.refdata import DECIMAL, EXACT, Answer, null_first
from benchmark.units._strata import (dec, desc_nulls_last, month_window,
                                     rank, rollup)


def reference(wh, params):
    days = month_window(wh, int(params["DMS"]))
    item = wh.table("item", ["i_item_sk", "i_category", "i_class"])
    ws = wh.table("web_sales", ["ws_sold_date_sk", "ws_item_sk",
                                "ws_net_paid"])
    j = ws.merge(days[["d_date_sk"]], left_on="ws_sold_date_sk",
                 right_on="d_date_sk") \
          .merge(item, left_on="ws_item_sk", right_on="i_item_sk")
    groups = rollup(j, ["i_category", "i_class"], "ws_net_paid")
    # (sum, category, class, lochierarchy, class rolled up)
    rows = [(s, key[0], key[1], sum(bits), bits[1])
            for key, bits, s, _n in groups]
    ranks = rank(rows, lambda r: (r[3], r[1] if r[4] == 0 else None),
                 lambda r: desc_nulls_last(r[0]))
    out = [(dec(r[0]), r[1], r[2], r[3], k) for r, k in zip(rows, ranks)]
    out.sort(key=lambda r: (-r[3],
                            null_first(r[1] if r[3] == 0 else None), r[4]))
    return Answer(["total_sum", "i_category", "i_class", "lochierarchy",
                   "rank_within_parent"],
                  [DECIMAL, EXACT, EXACT, EXACT, EXACT], out, limit=100,
                  sort_cols=(3, 1, 4))
