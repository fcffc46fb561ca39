"""The plain references of the inventory units (ISSUE 42: query72, query21,
query37, query82), each over a hand-made warehouse of a few dozen rows that
holds its hard points, against answers written out by hand, one case a hard
point: an item with stock in two warehouses and a sale in the same week (the
expansion), a sale whose week has no snapshot, ``inv_quantity_on_hand =
cs_quantity`` (the strict ``<``), a ship date exactly five days out, a NULL
``cs_promo_sk`` and a sale with no return (both outer joins), a tie at
query72's LIMIT, an exact 2/3 and 3/2 on query21's ratio and a zero
``inv_before``, a quantity of exactly 100 and 500 in query37 / query82, an
empty answer. Nothing of the program runs here."""
import datetime
import importlib
import os
import re
from decimal import Decimal

import pyarrow as pa
import pytest
from bench_helpers import BENCH
from test_benchmark_strata_references import ints, money, warehouse

from benchmark import compare, refdata

UNITS = ["query72", "query21", "query37", "query82"]
day = datetime.date


def columns(names: list, rows: list, types: dict = None) -> dict:
    """Rows written one a line -> the table's columns (int32 unless
    ``types`` names a maker for the column)."""
    out = {}
    for i, name in enumerate(names):
        make = (types or {}).get(name, ints)
        out[name] = make([r[i] for r in rows])
    return out


def answer(unit: str, wh, params: dict) -> refdata.Answer:
    """The reference's answer, with what its scans read beside it as
    ``reads``: {table: bytes}."""
    wh.unit, before = unit, len(wh.reads)
    ans = importlib.import_module(f"benchmark.units.{unit}").reference(
        wh, params)
    ans.reads = {table: n for _unit, table, n in wh.reads[before:]}
    return ans


@pytest.mark.parametrize("unit", UNITS + ["_inventory"])
def test_a_reference_imports_nothing_of_the_program(unit):
    with open(os.path.join(BENCH, "units", unit + ".py")) as f:
        text = f.read()
    assert "nds_tpu" not in text
    imported = re.findall(r"^(?:from|import)\s+([\w.]+)", text, re.M)
    assert set(imported) <= {"__future__", "datetime", "pandas",
                             "benchmark.refdata", "benchmark.units._strata",
                             "benchmark.units._inventory"}


# -- query72 -------------------------------------------------------------------

Q72 = {"YEAR": "2001", "BP": "501-1000", "MS": "D"}
#: sk 2 is exactly five days after sk 1 and sk 3 six; sk 4 opens week 11;
#: week 12 (sk 5) has no snapshot; sk 6 is week 10 of ANOTHER year; sk 7 has
#: no date
Q72_DATES = columns(
    ["d_date_sk", "d_date", "d_week_seq", "d_year"],
    [(1, day(2001, 1, 1), 10, 2001), (2, day(2001, 1, 6), 10, 2001),
     (3, day(2001, 1, 7), 10, 2001), (4, day(2001, 1, 8), 11, 2001),
     (5, day(2001, 1, 20), 12, 2001), (6, day(2000, 12, 31), 10, 2000),
     (7, None, 10, 2001), (8, day(2001, 2, 1), 14, 2001)],
    {"d_date": pa.array})
Q72_ITEMS = [(1, "A"), (2, "B"), (3, "C"), (4, "D"), (5, "E"), (6, None),
             (7, "G"), (8, "H"), (9, "I")]
#: (date, item, warehouse, quantity on hand)
Q72_STOCK = [
    (1, 1, 1, 5), (1, 1, 2, 9), (4, 1, 1, 1),   # A: two warehouses, a week
    (1, 2, 1, 1),                               # B: stock in week 10 only
    (1, 3, 1, 7), (1, 3, 2, 6),                 # C: 7 = cs_quantity, 6 < 7
    (1, 4, 1, 0),                               # D
    (1, 5, 1, 1),                               # E
    (1, 6, 3, 1),                               # NULL desc, NULL name
    (1, 7, 1, None), (1, 7, 2, 2),              # G: a NULL quantity
    (1, 8, 1, 1),                               # H
    (1, 9, 1, 1), (6, 9, 1, 1)]                 # I: two snapshots, one week
#: (sold, ship, cdemo, hdemo, item, promo, order, quantity)
Q72_SALES = [
    (1, 3, 1, 1, 1, 1, 100, 10),        # A: returned, promoted
    (5, 8, 1, 1, 2, 1, 101, 10),        # B: sold in week 12, no snapshot
    (1, 3, 1, 1, 3, 1, 102, 7),         # C
    (1, 2, 1, 1, 4, 1, 103, 1),         # D: shipped exactly five days out
    (1, 3, 1, 1, 4, 1, 104, 1),         # D: six days out
    (1, 3, 1, 1, 5, None, 105, 5),      # E: no promotion key
    (1, 3, 1, 1, 5, 99, 106, 5),        # E: a key no promotion holds
    (1, 3, 1, 1, 6, 1, 107, 5),         # NULL description, NULL name
    (1, 3, 1, 1, 7, 1, 108, 5),         # G
    (1, 3, None, 1, 8, 1, 109, 5),      # H: no demographics key
    (1, None, 1, 1, 8, 1, 110, 5),      # H: no ship date key
    (1, 7, 1, 1, 8, 1, 111, 5),         # H: the ship day has no date
    (6, 3, 1, 1, 8, 1, 112, 5),         # H: sold in week 10 of 2000
    (1, 3, 2, 1, 8, 1, 113, 5),         # H: marital status S
    (1, 3, 3, 1, 8, 1, 114, 5),         # H: marital status NULL
    (1, 3, 1, 2, 8, 1, 115, 5),         # H: buy potential >10000
    (1, 3, 1, 1, 8, 1, 116, None),      # H: no quantity
    (1, 3, 1, 1, 9, 1, 117, 5)]         # I: returned twice


@pytest.fixture(scope="module")
def q72(tmp_path_factory):
    wh = warehouse(
        tmp_path_factory.mktemp("q72"),
        customer_demographics={"cd_demo_sk": ints([1, 2, 3]),
                               "cd_marital_status": ["D", "S", None]},
        household_demographics={"hd_demo_sk": ints([1, 2]),
                                "hd_buy_potential": ["501-1000", ">10000"]},
        date_dim=Q72_DATES,
        warehouse={"w_warehouse_sk": ints([1, 2, 3]),
                   "w_warehouse_name": ["w1", "w2", None]},
        item=columns(["i_item_sk", "i_item_desc"], Q72_ITEMS,
                     {"i_item_desc": pa.array}),
        promotion={"p_promo_sk": ints([1, 2])},
        catalog_returns=columns(["cr_item_sk", "cr_order_number"],
                                [(1, 100), (9, 117), (9, 117), (2, 100),
                                 (None, 105)]),
        inventory=columns(["inv_date_sk", "inv_item_sk", "inv_warehouse_sk",
                           "inv_quantity_on_hand"], Q72_STOCK),
        catalog_sales=columns(
            ["cs_sold_date_sk", "cs_ship_date_sk", "cs_bill_cdemo_sk",
             "cs_bill_hdemo_sk", "cs_item_sk", "cs_promo_sk",
             "cs_order_number", "cs_quantity"], Q72_SALES))
    return answer("query72", wh, Q72)


@pytest.mark.parametrize("desc,rows", [
    # stock in two warehouses in the sale's week: the sale stands twice;
    # the snapshot of week 11 joins nothing
    ("A", [("A", "w1", 10, 0, 1, 1), ("A", "w2", 10, 0, 1, 1)]),
    # sold in week 12, which has no snapshot: week 10's stock is no match
    ("B", []),
    # 7 on hand is not less than 7 sold; 6 is
    ("C", [("C", "w2", 10, 0, 1, 1)]),
    # shipped exactly five days out: not "more than five"; six days is
    ("D", [("D", "w1", 10, 0, 1, 1)]),
    # a NULL promotion key and one no promotion holds: both kept, both
    # counted under no_promo; neither sale was returned and both stand once
    ("E", [("E", "w1", 10, 2, 0, 2)]),
    # a NULL description and a NULL warehouse name are groups
    (None, [(None, None, 10, 0, 1, 1)]),
    # a snapshot with no quantity passes no comparison
    ("G", [("G", "w2", 10, 0, 1, 1)]),
    # NULL keys, a NULL ship date, another year, other demographics and a
    # NULL quantity sold: every one joins or passes nothing
    ("H", []),
    # two returns of one (item, order) multiply the row; a snapshot of week
    # 10 of another year still matches on the week's number
    ("I", [("I", "w1", 10, 0, 4, 4)]),
], ids=lambda v: None if isinstance(v, list) else str(v))
def test_query72_hard_point(q72, desc, rows):
    assert [r for r in q72.rows if r[0] == desc] == rows


def test_query72_orders_by_the_count_down_then_the_whole_key_nulls_first(
        q72):
    assert q72.names == ["i_item_desc", "w_warehouse_name", "d_week_seq",
                         "no_promo", "promo", "total_cnt"]
    assert q72.kinds == [refdata.EXACT] * 6
    assert q72.limit == 100 and q72.sort_cols == (5, 0, 1, 2)
    assert [(r[5], r[0], r[1]) for r in q72.rows] == [
        (4, "I", "w1"), (2, "E", "w1"), (1, None, None), (1, "A", "w1"),
        (1, "A", "w2"), (1, "C", "w2"), (1, "D", "w1"), (1, "G", "w2")]


def test_query72_a_tie_at_the_limit_is_broken_by_the_whole_key(tmp_path):
    """120 items sold once each and one sold twice: 119 rows tie at count 1
    across the LIMIT, and the description decides which 99 of them stand."""
    n = 120
    sales = [(1, 2, 1, 1, i, None, i, 5) for i in range(1, n + 1)]
    sales.append((1, 2, 1, 1, 77, None, 1000, 5))
    wh = warehouse(
        tmp_path,
        customer_demographics={"cd_demo_sk": ints([1]),
                               "cd_marital_status": ["D"]},
        household_demographics={"hd_demo_sk": ints([1]),
                                "hd_buy_potential": ["501-1000"]},
        date_dim=columns(["d_date_sk", "d_date", "d_week_seq", "d_year"],
                         [(1, day(2001, 3, 1), 9, 2001),
                          (2, day(2001, 3, 9), 10, 2001)],
                         {"d_date": pa.array}),
        warehouse={"w_warehouse_sk": ints([1]), "w_warehouse_name": ["w"]},
        item={"i_item_sk": ints(list(range(1, n + 1))),
              "i_item_desc": [f"item {n - i:03d}" for i in range(n)]},
        promotion={"p_promo_sk": ints([1])},
        catalog_returns={"cr_item_sk": ints([]), "cr_order_number": ints([])},
        inventory=columns(["inv_date_sk", "inv_item_sk", "inv_warehouse_sk",
                           "inv_quantity_on_hand"],
                          [(1, i, 1, 1) for i in range(1, n + 1)]),
        catalog_sales=columns(
            ["cs_sold_date_sk", "cs_ship_date_sk", "cs_bill_cdemo_sk",
             "cs_bill_hdemo_sk", "cs_item_sk", "cs_promo_sk",
             "cs_order_number", "cs_quantity"], sales))
    ans = answer("query72", wh, Q72)
    assert len(ans.rows) == n
    assert ans.rows[0] == (f"item {n - 76:03d}", "w", 9, 2, 0, 2)
    rest = [f"item {k:03d}" for k in range(1, n + 1) if k != n - 76]
    assert [r[0] for r in ans.rows[1:]] == rest
    cmp_ = compare.Comparison(True, {"wrong_cells": 0, "decimal_err": 0,
                                     "float_rel_err": 1e-12})
    assert cmp_._window(ans) == (100, 100)     # no two rows tie on the key
    assert cmp_.check("cut", ans.rows[:100], ans)
    assert not cmp_.check("cut", ans.rows[:99] + ans.rows[100:101], ans)


# -- query21 -------------------------------------------------------------------

Q21 = {"YEAR": "2001", "MONTH": "3", "DAY": "24"}
AT = day(2001, 3, 24)
Q21_DATES = [(1, AT - datetime.timedelta(31)), (2, AT - datetime.timedelta(30)),
             (3, AT - datetime.timedelta(1)), (4, AT),
             (5, AT + datetime.timedelta(30)),
             (6, AT + datetime.timedelta(31)), (7, None)]
#: (key, id, price): 0.99 and 1.49 are inside the band, 0.98 and 1.50 not
Q21_ITEMS = [(1, "two thirds", "0.99"), (2, "three halves", "1.49"),
             (3, "just under", "1.00"), (4, "nothing before", "1.00"),
             (5, "cheap", "0.98"), (6, "dear", "1.50"), (7, "unpriced", None),
             (8, "edges", "1.00"), (9, "no quantity", "1.00"),
             (10, "all null before", "1.00"), (11, "nameless", "1.00"),
             (12, "equal", "1.20")]
#: (date, item, warehouse, quantity on hand)
Q21_STOCK = [
    (3, 1, 1, 3), (4, 1, 1, 2),             # 2/3 exactly
    (3, 2, 1, 2), (4, 2, 1, 3),             # 3/2 exactly
    (3, 3, 1, 30), (4, 3, 1, 19),           # 19/30 < 2/3
    (3, 3, 2, 20), (4, 3, 2, 31),           # 31/20 > 3/2
    (3, 4, 1, 0), (4, 4, 1, 5),             # inv_before = 0
    (3, 5, 1, 1), (4, 5, 1, 1), (3, 6, 1, 1), (4, 6, 1, 1),
    (3, 7, 1, 1), (4, 7, 1, 1),
    # day -31 and day +31 are outside, day -30 and +30 inside, the day
    # itself counts as after: before = 4, after = 1 + 4 = 5 -> 5/4
    (1, 8, 1, 100), (2, 8, 1, 4), (4, 8, 1, 1), (5, 8, 1, 4), (6, 8, 1, 100),
    (7, 8, 1, 100),
    (3, 9, 1, 6), (3, 9, 1, None), (4, 9, 1, None), (5, 9, 1, 5),
    (3, 10, 1, None),                       # before: NULL, after: no row
    (3, 11, 3, 1), (4, 11, 3, 1),           # the warehouse has no name
    (3, 12, 2, 7), (5, 12, 2, 7), (3, 12, None, 7)]


@pytest.fixture(scope="module")
def q21(tmp_path_factory):
    wh = warehouse(
        tmp_path_factory.mktemp("q21"),
        date_dim=columns(["d_date_sk", "d_date"], Q21_DATES,
                         {"d_date": pa.array}),
        warehouse={"w_warehouse_sk": ints([1, 2, 3]),
                   "w_warehouse_name": ["w1", "w2", None]},
        item=columns(["i_item_sk", "i_item_id", "i_current_price"],
                     Q21_ITEMS, {"i_item_id": pa.array,
                                 "i_current_price": money}),
        inventory=columns(["inv_date_sk", "inv_item_sk", "inv_warehouse_sk",
                           "inv_quantity_on_hand"], Q21_STOCK))
    return answer("query21", wh, Q21)


@pytest.mark.parametrize("item,rows", [
    ("two thirds", [("w1", "two thirds", 3, 2)]),
    ("three halves", [("w1", "three halves", 2, 3)]),
    ("just under", []),                 # 19/30 and 31/20: both just outside
    ("nothing before", []),             # inv_before = 0: the CASE is NULL
    ("cheap", []), ("dear", []), ("unpriced", []),
    ("edges", [("w1", "edges", 4, 5)]),
    # a NULL quantity adds nothing to either sum
    ("no quantity", [("w1", "no quantity", 6, 5)]),
    # every row before the day is NULL and none stands after: NULL > 0 fails
    ("all null before", []),
    ("nameless", [(None, "nameless", 1, 1)]),
    # a snapshot with no warehouse key joins nothing
    ("equal", [("w2", "equal", 7, 7)]),
], ids=lambda v: None if isinstance(v, list) else v.replace(" ", "_"))
def test_query21_hard_point(q21, item, rows):
    assert [r for r in q21.rows if r[1] == item] == rows


def test_query21_orders_by_the_warehouse_nulls_first_then_the_item(q21):
    assert q21.names == ["w_warehouse_name", "i_item_id", "inv_before",
                         "inv_after"]
    assert q21.kinds == [refdata.EXACT] * 4
    assert q21.limit == 100 and q21.sort_cols == (0, 1)
    assert [r[:2] for r in q21.rows] == [
        (None, "nameless"), ("w1", "edges"), ("w1", "no quantity"),
        ("w1", "three halves"), ("w1", "two thirds"), ("w2", "equal")]


# -- query37 and query82 -------------------------------------------------------

Q37 = {"PRICE": "20", "YEAR": "2000", "MONTH": "2", "DAY": "10",
       "M1": "11", "M2": "22", "M3": "33", "M4": "44"}
FROM = day(2000, 2, 10)
Q37_DATES = [(1, FROM - datetime.timedelta(1)), (2, FROM),
             (3, FROM + datetime.timedelta(60)),
             (4, FROM + datetime.timedelta(61)), (5, None)]
#: (key, id, description, price, manufacturer)
Q37_ITEMS = [
    (1, "I01", "a hundred", "20.00", 11), (2, "I02", "five hundred", "50.00",
                                           22),
    (3, "I03", "ninety-nine", "30.00", 33), (4, "I04", "501", "30.00", 44),
    (5, "I05", "a cent under", "19.99", 11), (6, "I06", "a cent over",
                                              "50.01", 11),
    (7, "I07", "another maker", "30.00", 55), (8, "I08", "no maker", "30.00",
                                               None),
    (9, "I09", "never sold", "30.00", 11), (10, "I10", "too early", "30.00",
                                            11),
    (11, "I11", "too late", "30.00", 11), (12, "I12", "many", "30.37", 22),
    (13, "I13", "shared", "25.00", 33), (14, "I13", "shared", "25.00", 33),
    (15, "I13", None, "25.00", 33), (16, "I16", "no stock", "30.00", 11),
    (17, "I17", "no quantity", "30.00", 11)]
#: (date, item, quantity on hand)
Q37_STOCK = [
    (2, 1, 100), (3, 2, 500), (2, 3, 99), (2, 4, 501), (2, 5, 200),
    (2, 6, 200), (2, 7, 200), (2, 8, 200), (2, 9, 200), (1, 10, 200),
    (4, 11, 200), (5, 11, 200), (2, 12, 150), (3, 12, 250), (2, 12, 350),
    (2, 13, 300), (3, 14, 300), (2, 15, 300), (2, 17, None), (None, 17, 200)]
Q37_SOLD = [1, 2, 3, 4, 5, 6, 7, 8, 10, 11, 12, 12, 12, 12, 13, 14, 15, 16,
            17, None]


@pytest.fixture(scope="module", params=[
    ("query37", "catalog_sales", "cs_item_sk"),
    ("query82", "store_sales", "ss_item_sk")], ids=lambda p: p[0])
def stocked(request, tmp_path_factory):
    unit, fact, col = request.param
    wh = warehouse(
        tmp_path_factory.mktemp(unit),
        date_dim=columns(["d_date_sk", "d_date"], Q37_DATES,
                         {"d_date": pa.array}),
        item=columns(["i_item_sk", "i_item_id", "i_item_desc",
                      "i_current_price", "i_manufact_id"], Q37_ITEMS,
                     {"i_item_id": pa.array, "i_item_desc": pa.array,
                      "i_current_price": money}),
        inventory=columns(["inv_date_sk", "inv_item_sk",
                           "inv_quantity_on_hand"], Q37_STOCK),
        **{fact: {col: ints(Q37_SOLD)}})
    return unit, wh


@pytest.mark.parametrize("item,rows", [
    # exactly 100 on the first day and exactly 500 on the sixtieth count,
    # and so do the prices at both ends of the band
    ("I01", [("I01", "a hundred", (2000, 2))]),
    ("I02", [("I02", "five hundred", (5000, 2))]),
    ("I03", []), ("I04", []),           # 99 and 501 on hand
    ("I05", []), ("I06", []),           # a cent outside the band
    ("I07", []), ("I08", []),           # another manufacturer, none
    ("I09", []),                        # in stock, never sold
    ("I10", []), ("I11", []),           # the day before, day 61, no date
    # three snapshots and four sales match: the item stands once
    ("I12", [("I12", "many", (3037, 2))]),
    # two items that share id, description and price are one group; a NULL
    # description is a group of its own, first
    ("I13", [("I13", None, (2500, 2)), ("I13", "shared", (2500, 2))]),
    ("I16", []),                        # sold, never stocked
    ("I17", []),                        # NULL quantity; NULL snapshot date
], ids=lambda v: None if isinstance(v, list) else v)
def test_query37_and_query82_hard_point(stocked, item, rows):
    unit, wh = stocked
    ans = answer(unit, wh, Q37)
    assert [r for r in ans.rows if r[0] == item] == rows
    assert ans.names == ["i_item_id", "i_item_desc", "i_current_price"]
    assert ans.kinds == [refdata.EXACT, refdata.EXACT, refdata.DECIMAL]
    assert ans.limit == 100 and ans.sort_cols == (0,)
    assert [r[0] for r in ans.rows] == ["I01", "I02", "I12", "I13", "I13"]


def test_an_empty_answer_is_an_answer(stocked):
    """Four manufacturers that made nothing: no row, and the comparison
    takes an empty result for it and nothing else."""
    unit, wh = stocked
    ans = answer(unit, wh, dict(Q37, M1="1", M2="2", M3="3", M4="4"))
    assert ans.rows == []
    cmp_ = compare.Comparison(True, {"wrong_cells": 0, "decimal_err": 0,
                                     "float_rel_err": 1e-12})
    assert cmp_.check("empty", [], ans)
    assert not cmp_.check("empty", [("I01", "a hundred", Decimal("20.00"))],
                          ans)


def test_the_price_is_the_controls_grip(stocked):
    """The four statements compute no decimal and no average: the one cell
    a lower precision can move is the price that query37 / query82 pass
    through, and bfloat16 moves it (30.37 reads 30.375; the whole dollars
    at the band's ends are bfloat16 numbers and stay)."""
    unit, wh = stocked
    ans = answer(unit, wh, Q37)
    rounded = compare.as_bf16(ans)
    cmp_ = compare.Comparison(True, {"wrong_cells": 0, "decimal_err": 0,
                                     "float_rel_err": 1e-12})
    assert [r[2] for r in rounded] == [20.0, 50.0, 30.375, 25.0, 25.0]
    assert not cmp_.check("bf16", rounded, ans)
    assert cmp_.wrong_cells == 0 and cmp_.decimal_err == 0.5


def test_query72_records_the_bytes_its_scans_read(q72):
    """``scan_roofline`` counts what the references read, each table once a
    read: ``date_dim`` stands once for its three aliases."""
    assert sorted(q72.reads) == [
        "catalog_returns", "catalog_sales", "customer_demographics",
        "date_dim", "household_demographics", "inventory", "item",
        "promotion", "warehouse"]
    assert all(n > 0 for n in q72.reads.values())


def test_query21_records_the_bytes_its_scans_read(q21):
    assert sorted(q21.reads) == ["date_dim", "inventory", "item",
                                 "warehouse"]
    # four int32 columns of inventory: 16 bytes a row
    assert q21.reads["inventory"] == 16 * len(Q21_STOCK)


def test_query37_and_query82_record_the_bytes_their_scans_read(stocked):
    unit, wh = stocked
    fact = "catalog_sales" if unit == "query37" else "store_sales"
    reads = answer(unit, wh, Q37).reads
    assert sorted(reads) == sorted(["date_dim", "inventory", "item", fact])
    assert reads["inventory"] == 12 * len(Q37_STOCK)
    assert reads[fact] == 4 * len(Q37_SOLD)
