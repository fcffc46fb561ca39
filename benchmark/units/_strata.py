"""What more than one reference of the strata units (query20, 22, 38, 47,
57, 76, 86, 93) needs, over ``refdata``: nothing here imports the program.

The SQL semantics the references pin, once:

* ascending keys put NULLs first, ``DESC`` keys put them last (the engine's
  and Spark's order);
* GROUP BY keeps a NULL key as a group of its own; ``SUM`` of no non-NULL
  value is NULL; ``AVG`` is the exact rational sum / count of the non-NULL
  values, rounded once where it becomes an answer's cell and never where it
  is compared or ordered;
* ``ROLLUP(k1..kn)`` emits the n + 1 prefixes of its keys; a rolled-up key
  reads NULL with its ``GROUPING()`` bit 1, a real NULL key reads NULL with
  the bit 0, and both rows stand in the answer;
* ``RANK()`` gives ties one rank and skips after them (1, 1, 3).
"""
from __future__ import annotations

from fractions import Fraction

from benchmark.refdata import (DECIMAL, EXACT, FLOAT, Answer, cell,
                               null_first, ratio)


def dec(v, scale: int = 2):
    """A DECIMAL cell ``(unscaled, scale)``; None for NULL."""
    v = cell(v)
    return None if v is None else (int(v), scale)


def desc_nulls_last(v):
    """Sort key of a ``DESC`` column of numbers: larger first, NULLs last."""
    return (1, 0) if v is None else (0, -v)


def month_window(wh, dms: int, columns: tuple = ()):
    """The ``date_dim`` rows with ``d_month_seq BETWEEN dms AND dms + 11``."""
    dt = wh.table("date_dim", ["d_date_sk", "d_month_seq", *columns])
    return dt[dt.d_month_seq.between(dms, dms + 11).fillna(False)]


def group_sums(frame, keys: list, value: str):
    """GROUP BY ``keys`` (a NULL key is a group): [(key cells, exact sum of
    the non-NULL ``value``s or None, their count)]; no key: one row."""
    if not keys:
        col = frame[value].dropna()
        return [((), int(col.sum()) if len(col) else None, len(col))]
    g = frame.groupby(keys, dropna=False)[value]
    sums, counts = g.sum(min_count=1), g.count()
    out = []
    for key, s, n in zip(sums.index, sums, counts):
        key = key if isinstance(key, tuple) else (key,)
        s = cell(s)
        out.append((tuple(cell(k) for k in key),
                    None if s is None else int(s), int(n)))
    return out


def rollup(frame, keys: list, value: str):
    """GROUP BY ROLLUP(``keys``): [(key cells with None where rolled up,
    GROUPING() bits, sum, count)], the full grouping first."""
    out = []
    for level in range(len(keys), -1, -1):
        rolled = len(keys) - level
        for key, s, n in group_sums(frame, keys[:level], value):
            out.append((key + (None,) * rolled,
                        (0,) * level + (1,) * rolled, s, n))
    return out


def rank(rows: list, partition, order) -> list:
    """``RANK() OVER (PARTITION BY partition(row) ORDER BY order(row))`` for
    every row, in the rows' order: ties share a rank, the next one skips."""
    parts: dict = {}
    for i, row in enumerate(rows):
        parts.setdefault(partition(row), []).append(i)
    out = [0] * len(rows)
    for members in parts.values():
        members.sort(key=lambda i: order(rows[i]))
        for pos, i in enumerate(members):
            tied = pos and order(rows[i]) == order(rows[members[pos - 1]])
            out[i] = out[members[pos - 1]] if tied else pos + 1
    return out


def monthly_outliers(wh, year: int, sales: tuple, dim: tuple) -> Answer:
    """The statement query47 and query57 share, over ``sales`` = (fact
    table, its date, item and price columns) and ``dim`` = (dimension table,
    its key, the fact's column for it, its name columns).

    ``v1``: monthly sums by (category, brand, names, year, month) of the year
    and its two neighbouring months; beside each its year's average — the
    exact rational (sum of the year's non-NULL monthly sums) / (their count)
    — and ``rn``, the rank of (year, month) among the names' months, which
    are distinct: 1, 2, 3 ... The self-joins at ``rn - 1`` / ``rn + 1`` are
    inner joins on the names: a month at either end of its partition drops
    out, a neighbour is the next month BY RANK whatever the calendar says,
    and a row with a NULL name joins nothing. The filter ``ABS(sum - avg) /
    avg > 0.1`` and the first ORDER BY key ``sum - avg`` are decided on exact
    integers and rationals (10 * |n * sum - S| > S with avg = S / n); only
    the answer's cell is the average rounded once."""
    fact, date_col, item_col, price = sales
    table, key, fact_key, name_cols = dim
    names = ["i_category", "i_brand"] + list(name_cols)
    k = len(names)
    dt = wh.table("date_dim", ["d_date_sk", "d_year", "d_moy"])
    dt = dt[((dt.d_year == year)
             | ((dt.d_year == year - 1) & (dt.d_moy == 12))
             | ((dt.d_year == year + 1) & (dt.d_moy == 1))).fillna(False)]
    item = wh.table("item", ["i_item_sk", "i_category", "i_brand"])
    named = wh.table(table, [key] + list(name_cols))
    f = wh.table(fact, [date_col, item_col, fact_key, price])
    j = f.merge(dt, left_on=date_col, right_on="d_date_sk") \
         .merge(item, left_on=item_col, right_on="i_item_sk") \
         .merge(named, left_on=fact_key, right_on=key)
    # v1: (names..., d_year, d_moy, sum_sales)
    v1 = [key_ + (s,) for key_, s, _n in group_sums(
        j, names + ["d_year", "d_moy"], price)]
    yearly: dict = {}
    for r in v1:
        if r[k + 2] is not None:
            tot = yearly.setdefault(r[:k + 1], [0, 0])
            tot[0] += r[k + 2]
            tot[1] += 1
    rn = rank(v1, lambda r: r[:k], lambda r: (r[k], r[k + 1]))
    at = {(r[:k], n): r for r, n in zip(v1, rn) if None not in r[:k]}
    keyed = []
    for r, n in zip(v1, rn):
        lag, lead = at.get((r[:k], n - 1)), at.get((r[:k], n + 1))
        if (r[:k], n) not in at or lag is None or lead is None:
            continue
        s, (total, months) = r[k + 2], yearly.get(r[:k + 1], (0, 0))
        if r[k] != year or s is None or total <= 0 \
                or not 10 * abs(months * s - total) > total:
            continue
        row = r[:k + 2] + (ratio(total, months, 2), dec(s), dec(lag[k + 2]),
                           dec(lead[k + 2]))
        keyed.append(((Fraction(months * s - total, months),
                       null_first(r[2])), row))
    rows = [row for _order, row in sorted(keyed, key=lambda kr: kr[0])]
    return Answer(names + ["d_year", "d_moy", "avg_monthly_sales",
                           "sum_sales", "psum", "nsum"],
                  [EXACT] * (k + 2) + [FLOAT, DECIMAL, DECIMAL, DECIMAL],
                  rows, limit=100, sort_cols=(k + 3, k + 2, 2))
