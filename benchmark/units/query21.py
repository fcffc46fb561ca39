"""Plain reference of TPC-DS query 21 (query21.tpl beside this file).

Pinned: the 61 days around the date count, both ends included; the price
band 0.99 to 1.49 is decided on cents. ``inv_before`` sums the quantities of
the snapshots before the date and 0 for each snapshot from it on (and
``inv_after`` the other way round), so a NULL quantity adds nothing and a
sum is NULL only where every row of the group gave NULL. The filter ``CASE
WHEN inv_before > 0 THEN inv_after / inv_before END BETWEEN 2.0 / 3.0 AND
3.0 / 2.0`` is decided on integers — ``2 * before <= 3 * after`` and ``2 *
after <= 3 * before`` with ``before > 0`` — so a ratio of exactly 2/3 or 3/2
stays, and a NULL or zero ``inv_before`` and a NULL ``inv_after`` go. A NULL
warehouse name is a group; ORDER BY puts it first."""
from benchmark.refdata import EXACT, Answer, cell, null_first
from benchmark.units._inventory import between, days, join, param_day


def _sum(values):
    """SUM over a group's CASE values: NULLs add nothing, all NULL is NULL."""
    kept = [int(v) for v in values if cell(v) is not None]
    return sum(kept) if kept else None


def reference(wh, params):
    day = param_day(params)
    item = wh.table("item", ["i_item_sk", "i_item_id", "i_current_price"])
    item = item[between(item.i_current_price, 99, 149)]
    dt = days(wh)
    dt = dt[between(dt.d_date, day - 30, day + 30)]
    house = wh.table("warehouse", ["w_warehouse_sk", "w_warehouse_name"])
    inv = wh.table("inventory", ["inv_date_sk", "inv_item_sk",
                                 "inv_warehouse_sk", "inv_quantity_on_hand"])
    inv = inv[inv.inv_date_sk.isin(dt.d_date_sk).fillna(False)
              & inv.inv_item_sk.isin(item.i_item_sk).fillna(False)]
    j = join(join(join(inv, dt, ["inv_date_sk"], ["d_date_sk"]),
                  item, ["inv_item_sk"], ["i_item_sk"]),
             house, ["inv_warehouse_sk"], ["w_warehouse_sk"])
    groups: dict = {}
    for name, iid, d, qty in zip(j.w_warehouse_name, j.i_item_id, j.d_date,
                                 j.inv_quantity_on_hand):
        before, after = groups.setdefault((cell(name), cell(iid)), ([], []))
        before.append(qty if d < day else 0)
        after.append(0 if d < day else qty)
    rows = []
    for key, (before, after) in groups.items():
        b, a = _sum(before), _sum(after)
        if b is not None and a is not None and b > 0 \
                and 2 * b <= 3 * a and 2 * a <= 3 * b:
            rows.append(key + (b, a))
    rows.sort(key=lambda r: (null_first(r[0]), null_first(r[1])))
    return Answer(["w_warehouse_name", "i_item_id", "inv_before",
                   "inv_after"], [EXACT] * 4, rows, limit=100,
                  sort_cols=(0, 1))
