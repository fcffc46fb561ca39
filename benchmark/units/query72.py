"""Plain reference of TPC-DS query 72 (query72.tpl beside this file).

The SQL joins ``catalog_sales`` to ``inventory`` on the item alone (about
650 snapshot rows an item at SF1) and brings the product back down in its
WHERE clause. The reference gives the same rows without ever holding that
product: the sales are cut first by their three dimension predicates
(marital status, buy potential, the year of ``d1``), which are plain inner
joins, and ``inventory`` then joins on (item, week), ``d1.d_week_seq =
d2.d_week_seq`` being an equality between the two sides.

Pinned: ``inv_quantity_on_hand < cs_quantity`` is strict, and NULL on
either side is no; ``d3.d_date > d1.d_date + 5 days`` on day numbers, so a
ship date exactly five days out goes; a sale with a NULL ship date, demo key
or warehouse joins nothing (inner joins). The two LEFT OUTER joins keep
every row: a NULL ``cs_promo_sk`` or one no promotion holds counts under
``no_promo``; a return multiplies a row only if several returns hold the
sale's (item, order) — ``catalog_returns``' key, so once at most in a sound
warehouse — and a sale with none stays once. GROUP BY keeps a NULL
description or warehouse name as a group. ORDER BY ``total_cnt`` DESC, then
the three group keys ascending with NULLs first: the key is whole, so no two
rows tie at the LIMIT."""
from benchmark.refdata import EXACT, Answer, cell, null_first
from benchmark.units._inventory import days, join


def reference(wh, params):
    year = int(params["YEAR"])
    cd = wh.table("customer_demographics", ["cd_demo_sk",
                                            "cd_marital_status"])
    cd = cd[(cd.cd_marital_status == params["MS"]).fillna(False)]
    hd = wh.table("household_demographics", ["hd_demo_sk",
                                             "hd_buy_potential"])
    hd = hd[(hd.hd_buy_potential == params["BP"]).fillna(False)]
    dt = days(wh, ("d_week_seq", "d_year"))
    d1 = dt[(dt.d_year == year).fillna(False)].rename(columns={
        "d_date_sk": "d1_sk", "d_date": "d1_date", "d_week_seq": "d1_week"})
    d3 = dt.rename(columns={"d_date_sk": "d3_sk", "d_date": "d3_date"})
    cs = wh.table("catalog_sales", [
        "cs_sold_date_sk", "cs_ship_date_sk", "cs_bill_cdemo_sk",
        "cs_bill_hdemo_sk", "cs_item_sk", "cs_promo_sk", "cs_order_number",
        "cs_quantity"])
    j = join(cs, d1[["d1_sk", "d1_date", "d1_week"]], ["cs_sold_date_sk"],
             ["d1_sk"])
    j = join(j, hd[["hd_demo_sk"]], ["cs_bill_hdemo_sk"], ["hd_demo_sk"])
    j = join(j, cd[["cd_demo_sk"]], ["cs_bill_cdemo_sk"], ["cd_demo_sk"])
    j = join(j, d3[["d3_sk", "d3_date"]], ["cs_ship_date_sk"], ["d3_sk"])
    j = j[(j.d3_date > j.d1_date + 5).fillna(False)]
    inv = wh.table("inventory", ["inv_date_sk", "inv_item_sk",
                                 "inv_warehouse_sk", "inv_quantity_on_hand"])
    d2 = dt[dt.d_week_seq.isin(j.d1_week.dropna()).fillna(False)].rename(
        columns={"d_date_sk": "d2_sk", "d_week_seq": "d2_week"})
    inv = inv[inv.inv_date_sk.isin(d2.d2_sk).fillna(False)
              & inv.inv_item_sk.isin(j.cs_item_sk.dropna()).fillna(False)]
    inv = join(inv, d2[["d2_sk", "d2_week"]], ["inv_date_sk"], ["d2_sk"])
    j = join(j, inv, ["cs_item_sk", "d1_week"], ["inv_item_sk", "d2_week"])
    j = j[(j.inv_quantity_on_hand < j.cs_quantity).fillna(False)]
    j = join(j, wh.table("warehouse", ["w_warehouse_sk",
                                       "w_warehouse_name"]),
             ["inv_warehouse_sk"], ["w_warehouse_sk"])
    j = join(j, wh.table("item", ["i_item_sk", "i_item_desc"]),
             ["cs_item_sk"], ["i_item_sk"])
    j = join(j, wh.table("promotion", ["p_promo_sk"]), ["cs_promo_sk"],
             ["p_promo_sk"], how="left")
    j = join(j, wh.table("catalog_returns", ["cr_item_sk",
                                             "cr_order_number"]),
             ["cs_item_sk", "cs_order_number"],
             ["cr_item_sk", "cr_order_number"], how="left")
    groups: dict = {}
    for desc, name, week, promo in zip(j.i_item_desc, j.w_warehouse_name,
                                       j.d1_week, j.p_promo_sk):
        counts = groups.setdefault((cell(desc), cell(name), cell(week)),
                                   [0, 0])
        counts[cell(promo) is not None] += 1
    rows = [key + (none, some, none + some)
            for key, (none, some) in groups.items()]
    rows.sort(key=lambda r: (-r[5], null_first(r[0]), null_first(r[1]),
                             null_first(r[2])))
    return Answer(["i_item_desc", "w_warehouse_name", "d_week_seq",
                   "no_promo", "promo", "total_cnt"], [EXACT] * 6, rows,
                  limit=100, sort_cols=(5, 0, 1, 2))
