"""The plain references of the strata units (ISSUE 32), each over a
hand-made warehouse of a few dozen rows that holds its stratum's hard point,
against answers written out by hand: a rollup subtotal whose NULL key stands
beside a real NULL, a ``RANK()`` tie, a NULL name on every side of the
INTERSECT, a sale with no return, a missing month at ``rn +- 1``, an exact
tie on the ratio filter and in an ordered average. Nothing of the program
runs here. (The references of query22, query47 and query76 are held here
too: the cell's mix does not run them yet, PERF.md PR 32.)"""
import datetime
import importlib
import os
from decimal import Decimal

import pyarrow as pa
import pyarrow.parquet as pq
import pytest
from bench_helpers import ROOT  # noqa: F401  (puts the checkout on sys.path)

from benchmark import refdata
from benchmark.units import _strata

MONEY = pa.decimal128(7, 2)


def money(values):
    return pa.array([None if v is None else Decimal(v) for v in values],
                    type=MONEY)


def ints(values):
    return pa.array(values, type=pa.int32())


def warehouse(tmp_path, **tables) -> refdata.Warehouse:
    for name, columns in tables.items():
        os.makedirs(tmp_path / name / "data")
        pq.write_table(pa.table(columns),
                       tmp_path / name / "data" / "part-0.parquet")
    return refdata.Warehouse(str(tmp_path))


def answer(unit: str, wh, params: dict) -> refdata.Answer:
    return importlib.import_module(f"benchmark.units.{unit}").reference(
        wh, params)


def test_rank_gives_ties_one_rank_and_skips_after_them():
    rows = [("a", 5), ("a", 7), ("a", 5), ("b", 1), ("a", 2), ("a", None)]
    got = _strata.rank(rows, lambda r: r[0],
                       lambda r: _strata.desc_nulls_last(r[1]))
    assert got == [2, 1, 2, 1, 4, 5]


def test_rollup_keeps_a_real_null_key_beside_the_rolled_up_one():
    import pandas as pd
    frame = pd.DataFrame({"k": pd.array(["x", None, "x"], dtype="string"),
                          "v": pd.array([1, 2, None], dtype="Int64")})
    assert _strata.rollup(frame, ["k"], "v") == [
        (("x",), (0,), 1, 1), ((None,), (0,), 2, 1), ((None,), (1,), 3, 2)]
    empty = frame[frame.v > 5]
    assert _strata.rollup(empty, ["k"], "v") == [((None,), (1,), None, 0)]


def test_query93_a_sale_with_no_return_and_a_return_of_null_quantity(
        tmp_path):
    wh = warehouse(
        tmp_path,
        reason={"r_reason_sk": ints([1, 2])},
        store_sales={
            "ss_item_sk": ints([1, 2, 3, 4, 5, 6]),
            "ss_ticket_number": ints([10, 10, 11, 12, 13, 14]),
            "ss_customer_sk": ints([100, 100, None, 200, 300, 100]),
            "ss_quantity": ints([5, 3, 4, None, 2, 1]),
            "ss_sales_price": money(["2.00", "1.50", "1.00", "3.00", "5.00",
                                     "0.50"])},
        store_returns={
            "sr_item_sk": ints([1, 3, 4, 5, 6]),
            "sr_ticket_number": ints([10, 11, 12, 13, 14]),
            "sr_reason_sk": ints([1, 1, 1, 2, 1]),
            "sr_return_quantity": ints([2, None, 1, 1, 1])})
    ans = answer("query93", wh, {"RID": "1"})
    # item 2 was never returned: the outer join keeps it, the WHERE drops
    # it; item 3's return has no quantity: the whole sale counts; item 4
    # has no quantity: customer 200's sum is NULL, first in the order
    assert ans.rows == [(200, None), (None, (400, 2)), (100, (600, 2))]
    assert ans.limit == 100 and ans.sort_cols == (1, 0)
    assert ans.kinds == [refdata.EXACT, refdata.DECIMAL]


def test_query20_the_window_sums_a_class_and_a_null_class_is_its_own(
        tmp_path):
    day = datetime.date
    wh = warehouse(
        tmp_path,
        date_dim={"d_date_sk": ints([1, 2, 3]),
                  "d_date": pa.array([day(2001, 2, 23), day(2001, 3, 25),
                                      day(2001, 3, 26)])},
        item={"i_item_sk": ints([1, 2, 3, 4, 5]),
              "i_item_id": ["A", "B", "C", "D", "E"],
              "i_item_desc": ["da", "db", "dc", "dd", "de"],
              "i_category": ["Men", "Men", "Home", "Toys", "Men"],
              "i_class": ["c1", "c1", None, "c1", "c2"],
              "i_current_price": money(["1.00", "2.00", "3.00", "4.00",
                                        "5.00"])},
        catalog_sales={
            "cs_sold_date_sk": ints([1, 2, 3, 1, 2, 1, None, 1]),
            "cs_item_sk": ints([1, 1, 1, 2, 3, 4, 5, 5]),
            "cs_ext_sales_price": money(["10.00", "20.00", "99.00", "10.00",
                                         "7.00", "50.00", "5.00", None])})
    ans = answer("query20", wh, {"YEAR": "2001", "MONTH": "2", "DAY": "23",
                                 "CATS": "'Men', 'Home', 'Music'"})
    # the last day of the 30 counts, the 31st does not; Toys is filtered
    # before the window, so class c1 sums 40.00; item E's only priced sale
    # has no date, so its revenue, its class's and its ratio are NULL
    assert ans.rows == [
        ("C", "dc", "Home", None, (300, 2), (700, 2), 100.0),
        ("A", "da", "Men", "c1", (100, 2), (3000, 2), 75.0),
        ("B", "db", "Men", "c1", (200, 2), (1000, 2), 25.0),
        ("E", "de", "Men", "c2", (500, 2), None, None)]
    assert ans.sort_cols == (2, 3, 0, 1, 6)


def test_query86_subtotals_stand_beside_real_nulls_and_ranks_tie(tmp_path):
    wh = warehouse(
        tmp_path,
        date_dim={"d_date_sk": ints([1, 2]),
                  "d_month_seq": ints([1200, 1212])},
        item={"i_item_sk": ints([1, 2, 3, 4, 5]),
              "i_category": ["Books", "Books", "Books", "Music", None],
              "i_class": ["fiction", "poetry", None, "pop", "rock"]},
        web_sales={
            "ws_sold_date_sk": ints([1, 1, 1, 1, 1, 1, 2, None]),
            "ws_item_sk": ints([1, 1, 2, 3, 4, 5, 1, 1]),
            "ws_net_paid": money(["10.00", "5.00", "15.00", "2.00", "40.00",
                                  "1.00", "100.00", "100.00"])})
    ans = answer("query86", wh, {"DMS": "1200"})
    assert ans.names == ["total_sum", "i_category", "i_class",
                         "lochierarchy", "rank_within_parent"]
    assert ans.rows == [
        ((7300, 2), None, None, 2, 1),
        # the categories' subtotals: one partition, ranked by their sums;
        # the NULL category's subtotal reads (NULL, NULL) like the total
        ((4000, 2), "Music", None, 1, 1),
        ((3200, 2), "Books", None, 1, 2),
        ((100, 2), None, None, 1, 3),
        # the leaves, by category with the real NULL first; fiction and
        # poetry tie at rank 1, the class that is a real NULL takes rank 3
        # and reads like Books' subtotal but for its GROUPING() bits
        ((100, 2), None, "rock", 0, 1),
        ((1500, 2), "Books", "fiction", 0, 1),
        ((1500, 2), "Books", "poetry", 0, 1),
        ((200, 2), "Books", None, 0, 3),
        ((4000, 2), "Music", "pop", 0, 1)]
    assert ans.sort_cols == (3, 1, 4)


def test_query38_a_null_name_on_every_side_is_in_the_intersection(tmp_path):
    day = datetime.date
    wh = warehouse(
        tmp_path,
        date_dim={"d_date_sk": ints([1, 2, 3]),
                  "d_month_seq": ints([1200, 1200, 1217]),
                  "d_date": pa.array([day(2000, 1, 1), day(2000, 1, 2),
                                      day(2001, 6, 1)])},
        customer={"c_customer_sk": ints([1, 2, 3, 4, 5]),
                  "c_last_name": ["Smith", None, "Jones", "Smith", "Lee"],
                  "c_first_name": ["Ann", "Bob", None, "Ann", "Cy"]},
        store_sales={"ss_sold_date_sk": ints([1, 1, 2, 1, 1, None, 1]),
                     "ss_customer_sk": ints([1, 2, 3, 5, 1, 1, None])},
        catalog_sales={"cs_sold_date_sk": ints([1, 1, 2, 2]),
                       "cs_bill_customer_sk": ints([4, 2, 3, 5])},
        web_sales={"ws_sold_date_sk": ints([1, 1, 2, 1, 3]),
                   "ws_bill_customer_sk": ints([1, 2, 3, 5, 1])})
    ans = answer("query38", wh, {"DMS": "1200"})
    # (Smith, Ann, day 1) through two customers of one name, (NULL, Bob,
    # day 1) and (Jones, NULL, day 2): NULL equals NULL in a set operation;
    # Lee bought on day 1, day 2, day 1: never all three on one day
    assert ans.rows == [(3,)] and ans.kinds == [refdata.EXACT]


def test_query47_neighbours_are_by_rank_and_the_ratio_filter_is_exact(
        tmp_path):
    months = [(1999, 11), (1999, 12), (2000, 1), (2000, 2), (2000, 3),
              (2000, 4), (2000, 5), (2000, 6), (2001, 1), (2001, 2)]
    # store 1's monthly sums: 1999-12 60, then 100, 110, 90, 120, (no May),
    # 80, and 2001-01 70; store 2 has no company name
    sales = [(2, 1, "60.00"), (3, 1, "40.00"), (3, 1, "60.00"),
             (3, 1, None), (4, 1, "110.00"), (5, 1, "90.00"),
             (6, 1, "120.00"), (8, 1, "80.00"), (9, 1, "70.00"),
             (1, 1, "999.00"), (10, 1, "999.00"), (None, 1, "5.00"),
             (3, 2, "10.00"), (4, 2, "50.00"), (5, 2, "10.00")]
    wh = warehouse(
        tmp_path,
        date_dim={"d_date_sk": ints(list(range(1, 11))),
                  "d_year": ints([y for y, _m in months]),
                  "d_moy": ints([m for _y, m in months])},
        item={"i_item_sk": ints([1]), "i_category": ["C"], "i_brand": ["B"]},
        store={"s_store_sk": ints([1, 2]), "s_store_name": ["S", "T"],
               "s_company_name": ["Co", None]},
        store_sales={"ss_sold_date_sk": ints([s[0] for s in sales]),
                     "ss_item_sk": ints([1] * len(sales)),
                     "ss_store_sk": ints([s[1] for s in sales]),
                     "ss_sales_price": money([s[2] for s in sales])})
    ans = answer("query47", wh, {"YEAR": "2000"})
    # the year's average is 500 / 5 = 100: February (110) and March (90)
    # stand at exactly 0.1 and are NOT over it (a double reads 110 / 100 - 1
    # as 0.10000000000000009); April's lead is June, its neighbour by rank
    # with May missing; January's deviation is 0; store 2's NULL company
    # name joins nothing
    assert ans.rows == [
        ("C", "B", "S", "Co", 2000, 6, 100.0, (8000, 2), (12000, 2),
         (7000, 2)),
        ("C", "B", "S", "Co", 2000, 4, 100.0, (12000, 2), (9000, 2),
         (8000, 2))]
    assert ans.sort_cols == (7, 6, 2) and ans.limit == 100


def test_query57_is_query47s_statement_over_the_catalog_channel(tmp_path):
    months = [(1999, 12), (2000, 1), (2000, 2), (2000, 3), (2001, 1)]
    # center 1: 10, then 30, 50, 40, then 20: the year's average is 40
    sales = [(1, 1, "10.00"), (2, 1, "30.00"), (3, 1, "50.00"),
             (4, 1, "40.00"), (5, 1, "20.00"), (3, None, "77.00"),
             (2, 2, "5.00"), (3, 2, "500.00"), (4, 2, "5.00")]
    wh = warehouse(
        tmp_path,
        date_dim={"d_date_sk": ints([1, 2, 3, 4, 5]),
                  "d_year": ints([y for y, _m in months]),
                  "d_moy": ints([m for _y, m in months])},
        item={"i_item_sk": ints([1]), "i_category": ["C"], "i_brand": ["B"]},
        call_center={"cc_call_center_sk": ints([1, 2]),
                     "cc_name": ["north", None]},
        catalog_sales={"cs_sold_date_sk": ints([s[0] for s in sales]),
                       "cs_item_sk": ints([1] * len(sales)),
                       "cs_call_center_sk": ints([s[1] for s in sales]),
                       "cs_sales_price": money([s[2] for s in sales])})
    ans = answer("query57", wh, {"YEAR": "2000"})
    # March sits on the average; a sale with no call center and the center
    # with no name join nothing; January (-10) sorts before February (+10)
    assert ans.names == ["i_category", "i_brand", "cc_name", "d_year",
                         "d_moy", "avg_monthly_sales", "sum_sales", "psum",
                         "nsum"]
    assert ans.rows == [
        ("C", "B", "north", 2000, 1, 40.0, (3000, 2), (1000, 2), (5000, 2)),
        ("C", "B", "north", 2000, 2, 40.0, (5000, 2), (3000, 2), (4000, 2))]
    assert ans.sort_cols == (6, 5, 2)


def test_query22_equal_averages_of_unequal_sums_tie_and_names_decide(
        tmp_path):
    inv = [(1, 1, 3), (1, 1, 3), (1, 1, 4),
           (1, 2, 3), (1, 2, 4), (1, 2, 3), (1, 2, 3), (1, 2, 4), (1, 2, 3),
           (1, 3, 1), (1, 3, None), (1, 4, None), (2, 1, 1000)]
    wh = warehouse(
        tmp_path,
        date_dim={"d_date_sk": ints([1, 2]),
                  "d_month_seq": ints([1211, 1212])},
        item={"i_item_sk": ints([1, 2, 3, 4]),
              "i_product_name": ["p1", "p2", "p3", "p4"],
              "i_brand": ["b1", "b1", "b3", "b4"],
              "i_class": ["c1", "c1", "c3", "c4"],
              "i_category": ["cat1", "cat1", None, "cat4"]},
        inventory={"inv_date_sk": ints([r[0] for r in inv]),
                   "inv_item_sk": ints([r[1] for r in inv]),
                   "inv_quantity_on_hand": ints([r[2] for r in inv])})
    ans = answer("query22", wh, {"DMS": "1200"})
    third = 10 / 3
    assert ans.rows == [
        # no quantity at all: a NULL average, first; NULL names first
        ("p4", None, None, None, None), ("p4", "b4", None, None, None),
        ("p4", "b4", "c4", None, None), ("p4", "b4", "c4", "cat4", None),
        # p3's category is a real NULL: its leaf reads like its subtotal
        ("p3", None, None, None, 1.0), ("p3", "b3", None, None, 1.0),
        ("p3", "b3", "c3", None, 1.0), ("p3", "b3", "c3", None, 1.0),
        (None, None, None, None, 3.1),
        # 10 / 3 and 20 / 6 are one rational: the names decide
        ("p1", None, None, None, third), ("p1", "b1", None, None, third),
        ("p1", "b1", "c1", None, third), ("p1", "b1", "c1", "cat1", third),
        ("p2", None, None, None, third), ("p2", "b1", None, None, third),
        ("p2", "b1", "c1", None, third), ("p2", "b1", "c1", "cat1", third)]
    assert ans.sort_cols == (4, 0, 1, 2, 3)


def test_query76_null_keys_select_and_a_null_category_is_a_group(tmp_path):
    wh = warehouse(
        tmp_path,
        date_dim={"d_date_sk": ints([1, 2]), "d_year": ints([2000, 2000]),
                  "d_qoy": ints([1, 2])},
        item={"i_item_sk": ints([1, 2]), "i_category": ["catA", None]},
        store_sales={"ss_hdemo_sk": ints([None, None, 5, None, None]),
                     "ss_sold_date_sk": ints([1, 1, 1, None, 2]),
                     "ss_item_sk": ints([1, 1, 1, 1, 2]),
                     "ss_ext_sales_price": money(["1.00", None, "9.00",
                                                  "3.00", "2.00"])},
        web_sales={"ws_web_site_sk": ints([None, 7]),
                   "ws_sold_date_sk": ints([1, 1]),
                   "ws_item_sk": ints([2, 2]),
                   "ws_ext_sales_price": money(["4.00", "8.00"])},
        catalog_sales={"cs_warehouse_sk": ints([None]),
                       "cs_sold_date_sk": ints([2]),
                       "cs_item_sk": ints([1]),
                       "cs_ext_sales_price": money([None])})
    ans = answer("query76", wh, {"NULLCOLSS": "ss_hdemo_sk",
                                 "NULLCOLWS": "ws_web_site_sk",
                                 "NULLCOLCS": "cs_warehouse_sk"})
    assert ans.rows == [
        ("catalog", "cs_warehouse_sk", 2000, 2, "catA", 1, None),
        ("store", "ss_hdemo_sk", 2000, 1, "catA", 2, (100, 2)),
        ("store", "ss_hdemo_sk", 2000, 2, None, 1, (200, 2)),
        ("web", "ws_web_site_sk", 2000, 1, None, 1, (400, 2))]
    assert ans.kinds == [refdata.EXACT] * 6 + [refdata.DECIMAL]


@pytest.mark.parametrize("unit", ["query20", "query86", "query38", "query93",
                                  "query47", "query57", "query22",
                                  "query76"])
def test_a_reference_imports_nothing_of_the_program_and_says_what_it_pins(
        unit):
    with open(os.path.join(ROOT, "benchmark", "units", unit + ".py")) as f:
        text = f.read()
    assert "nds_tpu" not in text and "Pinned:" in text
    with open(os.path.join(ROOT, "benchmark", "units", "_strata.py")) as f:
        assert "nds_tpu" not in f.read()
