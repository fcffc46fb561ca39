"""What more than one reference of the inventory units (query21, 37, 72, 82)
needs, over ``refdata``: nothing here imports the program.

The SQL semantics the references pin, once:

* a join key that is NULL equals nothing, on either side (pandas' ``merge``
  would pair NULL with NULL): ``join`` drops such rows of the right side
  always and of the left side unless the join is LEFT OUTER, where they stay
  with a NULL right side;
* a date is compared as its day number (``date.toordinal()``), so ``+
  INTERVAL n DAYS`` is an integer addition; a NULL date passes no filter;
* ascending keys put NULLs first, ``DESC`` keys put them last.
"""
from __future__ import annotations

import datetime

import pandas as pd

from benchmark.refdata import DECIMAL, EXACT, Answer, cell, null_first
from benchmark.units._strata import dec


def param_day(params: dict) -> int:
    """The day number of the template's ``'[YEAR]-0[MONTH]-[DAY]'``."""
    return datetime.date(int(params["YEAR"]), int(params["MONTH"]),
                         int(params["DAY"])).toordinal()


def days(wh, columns: tuple = ()) -> pd.DataFrame:
    """``date_dim`` with ``d_date`` as a nullable day number."""
    dt = wh.table("date_dim", ["d_date_sk", "d_date", *columns])
    return dt.assign(d_date=pd.array(
        [None if d is None or d != d else d.toordinal() for d in dt.d_date],
        dtype="Int64"))


def between(col: pd.Series, lo: int, hi: int) -> pd.Series:
    """``col BETWEEN lo AND hi`` as a plain mask (NULL is no)."""
    return ((col >= lo) & (col <= hi)).fillna(False).astype(bool)


def join(left: pd.DataFrame, right: pd.DataFrame, left_on: list,
         right_on: list, how: str = "inner") -> pd.DataFrame:
    right = right.dropna(subset=right_on)
    if how == "inner":
        return left.dropna(subset=left_on).merge(
            right, left_on=left_on, right_on=right_on)
    keyed = left[left_on].notna().all(axis=1)
    out = left[keyed].merge(right, how="left", left_on=left_on,
                            right_on=right_on)
    return pd.concat([out, left[~keyed]], ignore_index=True)


def stocked_items(wh, params: dict, fact: str, item_col: str) -> Answer:
    """The statement query37 and query82 share, over ``fact`` and its item
    column: the items of four manufacturers in a 30-dollar price band that
    had between 100 and 500 units on hand in some snapshot of the 60 days
    from the date AND were sold through the channel at any time. Both joins
    are M:N and the GROUP BY collapses them, so an item stands once however
    many snapshots and sales it matched; items that share ``i_item_id``,
    description and price are one group. The price band is decided on cents,
    the quantities 100 and 500 count, and so do the first and the 60th day."""
    price, start = int(params["PRICE"]), param_day(params)
    makers = [int(params[f"M{i}"]) for i in (1, 2, 3, 4)]
    item = wh.table("item", ["i_item_sk", "i_item_id", "i_item_desc",
                             "i_current_price", "i_manufact_id"])
    item = item[between(item.i_current_price, price * 100,
                        (price + 30) * 100)
                & item.i_manufact_id.isin(makers).fillna(False)]
    dt = days(wh)
    dt = dt[between(dt.d_date, start, start + 60)]
    inv = wh.table("inventory", ["inv_date_sk", "inv_item_sk",
                                 "inv_quantity_on_hand"])
    inv = inv[between(inv.inv_quantity_on_hand, 100, 500)
              & inv.inv_date_sk.isin(dt.d_date_sk).fillna(False)]
    sold = wh.table(fact, [item_col])
    kept = item[item.i_item_sk.isin(inv.inv_item_sk.dropna())
                & item.i_item_sk.isin(sold[item_col].dropna())]
    groups = {(cell(i), cell(d), cell(p)) for i, d, p in zip(
        kept.i_item_id, kept.i_item_desc, kept.i_current_price)}
    rows = sorted(((i, d, dec(p)) for i, d, p in groups),
                  key=lambda r: tuple(map(null_first, r)))
    return Answer(["i_item_id", "i_item_desc", "i_current_price"],
                  [EXACT, EXACT, DECIMAL], rows, limit=100, sort_cols=(0,))
