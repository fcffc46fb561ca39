"""``Planner._join_units`` joins a fact to its own dimensions before it joins
two facts (ISSUE 43).

An equality edge whose key on one endpoint is a single column the catalog
declares unique for that unit's base table is a *dimension edge*; the
dimension edges alone cut a FROM / WHERE graph into *stars*. One star, or no
star besides the largest unit's with a second unit: the left-deep greedy
spine the planner always built (``_join_greedy`` over the whole graph), node
for node. Otherwise every star is joined on its own and the stars are joined
with every edge between two of them as a key column of ONE ``JoinNode``
(``star_build``). Planner-only over the schema, no data — but for the last
test, query72 at SF0.01 against SQLite.
"""
import os

import pytest

from benchmark import traffic
from nds_tpu import streams
from nds_tpu.engine import plan as P
from nds_tpu.engine.arrow_bridge import engine_schema
from nds_tpu.engine.planner import Catalog, Planner
from nds_tpu.engine.verify import plan_fingerprint
from nds_tpu.power import strip_sql_comments
from nds_tpu.schema import UNIQUE_KEYS, get_schemas
from nds_tpu.sql import parse_sql

#: TPC-DS v3.2.0 table 3-2, scale factor 1: what a warehouse's Parquet
#: footers tell the planner in the benchmark's cells
SF1_ROWS = {
    "call_center": 6, "catalog_page": 11718, "catalog_returns": 144067,
    "catalog_sales": 1441548, "customer": 100000, "customer_address": 50000,
    "customer_demographics": 1920800, "date_dim": 73049,
    "household_demographics": 7200, "income_band": 20,
    "inventory": 11745000, "item": 18000, "promotion": 300, "reason": 35,
    "ship_mode": 20, "store": 12, "store_returns": 287514,
    "store_sales": 2880404, "time_dim": 86400, "warehouse": 5,
    "web_page": 60, "web_returns": 71763, "web_sales": 719384,
    "web_site": 30,
}
#: the templates whose graphs hold two stars of which one besides the
#: spine's has a second unit, at SF1's row counts (ISSUE 43's census): 17,
#: 25, 29 (store_sales / store_returns / catalog_sales, each with its own
#: filtered date_dim), 44 (two ranked sub-selects, each with item), 50
#: (store_sales + store + date_dim against store_returns + date_dim), 54, 59
#: (a CTE + store against date_dim), 72, 85 (web_sales + 2 dimensions against
#: web_returns + 5)
ENGAGE = (17, 25, 29, 44, 50, 54, 59, 72, 85)
UNITS_DIR = os.path.join(os.path.dirname(traffic.__file__), "units")
OTHER_UNITS = sorted(f[:-4] for f in os.listdir(UNITS_DIR)
                     if f.endswith(".tpl") and f != "query72.tpl")


def catalog() -> Catalog:
    """The 24 tables at SF1's row counts, every rewrite pass verified."""
    tables = {}
    for name, sch in get_schemas(use_decimal=True).items():
        names, dtypes = engine_schema(sch.arrow_schema(use_decimal=True), True)
        tables[name] = (names, dtypes, SF1_ROWS[name])
    return Catalog(tables, dec_enabled=True, unique_cols=dict(UNIQUE_KEYS),
                   verify_plans="per-pass")


def greedy_only(self, units, edges, ctes, outer):
    """What ``_join_units`` was before ISSUE 43: the kept single-star
    routine over the whole graph, from the largest unit."""
    everyone = list(range(len(units)))
    spine = max(everyone, key=lambda i: units[i].est_rows)
    return self._join_greedy(units, edges, everyone, spine, ctes, outer)


def plan(sql: str, cat: Catalog, before: bool = False) -> P.PlanNode:
    planner = Planner(cat)
    if before:
        planner._join_units = greedy_only.__get__(planner)
    return planner.plan_query(parse_sql(sql))


def star_joins(root: P.PlanNode) -> list:
    return [n for n in P.iter_plan_nodes(root)
            if isinstance(n, P.JoinNode) and n.star_build]


def scans(root: P.PlanNode) -> list:
    return sorted(n.table for n in P.iter_plan_nodes(root)
                  if isinstance(n, P.ScanNode))


def template_statements(number):
    sql = streams.instantiate(number, stream=0, rngseed=31415)
    parts = (streams.split_special_query(f"query{number}", sql)
             if number in streams.SPECIAL_TEMPLATES
             else [(f"query{number}", sql)])
    for _name, part in parts:
        for stmt in strip_sql_comments(part).split(";"):
            if stmt.strip():
                yield stmt


def query72_sql() -> str:
    return traffic.instantiate("query72", 1).sql


# -- the graph: edges, stars ------------------------------------------------

def query72_graph(cat):
    """(planner, units, edges, ctes, outer) of query72's inner join group,
    as ``_plan_from_where`` hands them to ``_join_units``."""
    planner = Planner(cat)
    seen = []
    whole = planner._join_units

    def spy(units, edges, ctes, outer):
        seen.append((units, edges, ctes, outer))
        return whole(units, edges, ctes, outer)
    planner._join_units = spy
    planner.plan_query(parse_sql(query72_sql()))
    assert len(seen) == 1
    return (planner,) + seen[0]


def unit_name(unit) -> str:
    return unit.entries[0].qualifier


def test_query72s_edges_are_seven_dimension_edges_and_two_many_to_many():
    planner, units, edges, ctes, outer = query72_graph(catalog())
    assert [unit_name(u) for u in units] == [
        "catalog_sales", "inventory", "warehouse", "item",
        "customer_demographics", "household_demographics", "d1", "d2", "d3"]
    kinds = {}
    for a, b, le, re in edges:
        dim = planner._unit_key_is_unique(units[a], le, ctes, outer) or \
            planner._unit_key_is_unique(units[b], re, ctes, outer)
        kinds[frozenset((unit_name(units[a]), unit_name(units[b])))] = dim
    many = {pair for pair, dim in kinds.items() if not dim}
    assert many == {frozenset(("catalog_sales", "inventory")),
                    frozenset(("d1", "d2"))}
    assert len(kinds) == 9
    # a filter pushed into the unit keeps its key unique: d1 carries
    # d_year = 2001, the two demographics their literals
    assert isinstance(units[6].plan, P.FilterNode)
    assert kinds[frozenset(("catalog_sales", "d1"))]


def test_query72s_graph_falls_into_two_stars():
    planner, units, edges, ctes, outer = query72_graph(catalog())
    stars = [sorted(unit_name(units[i]) for i in s)
             for s in planner._stars(units, edges, ctes, outer)]
    assert stars == [
        ["catalog_sales", "customer_demographics", "d1", "d3",
         "household_demographics", "item"],
        ["d2", "inventory", "warehouse"]]


def test_a_star_is_sized_by_its_fact_and_its_filtered_dimensions():
    planner, units, edges, ctes, outer = query72_graph(catalog())
    cs, inv = planner._stars(units, edges, ctes, outer)
    # catalog_sales with three filtered dimensions (cd, hd, d1): 5^3
    assert planner._star_est(units, cs) == pytest.approx(1441548 / 125)
    assert planner._star_est(units, inv) == 11745000


# -- the tree ---------------------------------------------------------------

def test_query72_joins_each_fact_to_its_dimensions_and_then_the_two_facts():
    root = plan(query72_sql(), catalog())
    (star,) = star_joins(root)
    assert star.kind == "inner" and star.residual is None
    # one composite-key join: (week, item) on both sides
    assert sorted(k.name for k in star.left_keys) == \
        ["d_week_seq", "inv_item_sk"]
    assert sorted(k.name for k in star.right_keys) == \
        ["cs_item_sk", "d_week_seq"]
    for lk, rk in zip(star.left_keys, star.right_keys):
        assert (lk.name, rk.name) in (("inv_item_sk", "cs_item_sk"),
                                      ("d_week_seq", "d_week_seq"))
    # the build side is the catalog_sales star's own join tree, d1 and its
    # year filter inside it
    assert isinstance(star.right, P.JoinNode)
    assert scans(star.right) == [
        "catalog_sales", "customer_demographics", "date_dim", "date_dim",
        "household_demographics", "item"]
    years = [n for n in P.iter_plan_nodes(star.right)
             if isinstance(n, P.FilterNode)
             and isinstance(n.child, P.ScanNode)
             and n.child.table == "date_dim"]
    assert len(years) == 1
    # the probe spine is inventory with warehouse and d2, never d1: one
    # date_dim, joined on inv_date_sk, unfiltered
    assert scans(star.left) == ["date_dim", "inventory", "warehouse"]
    assert not [n for n in P.iter_plan_nodes(star.left)
                if isinstance(n, P.FilterNode)]
    spine_keys = sorted(k.name for n in P.iter_plan_nodes(star.left)
                        if isinstance(n, P.JoinNode) for k in n.left_keys)
    assert spine_keys == ["inv_date_sk", "inv_warehouse_sk"]
    # every join below the star join is a single-key dimension join
    for side in (star.left, star.right):
        for n in P.iter_plan_nodes(side):
            if isinstance(n, P.JoinNode):
                assert len(n.left_keys) == 1 and not n.star_build
    # the two outer joins still stand above it, in syntax order
    outer = [n for n in P.iter_plan_nodes(root)
             if isinstance(n, P.JoinNode) and n.kind == "left"]
    assert [scans(n.right) for n in outer] == [["catalog_returns"],
                                               ["promotion"]]
    assert outer[1].left is star


def test_query72_still_defers_the_warehouse_name_and_carries_the_item_desc():
    """``_late_materialization`` walks the probe spine: ``warehouse`` is on
    it and is gathered after the aggregate as before; ``item`` now sits in
    the build side's tree, so ``i_item_desc`` rides the 8,648-row build side
    (dictionary codes) instead of being gathered at 998 groups."""
    root = plan(query72_sql(), catalog())
    late = [n for n in P.iter_plan_nodes(root)
            if isinstance(n, P.JoinNode) and n.late_mat]
    assert [scans(n.right) for n in late] == [["warehouse"]]
    before = plan(query72_sql(), catalog(), before=True)
    late = [n for n in P.iter_plan_nodes(before)
            if isinstance(n, P.JoinNode) and n.late_mat]
    assert sorted(scans(n.right)[0] for n in late) == ["item", "warehouse"]


# -- everything else plans as it did ---------------------------------------

@pytest.mark.parametrize("unit", OTHER_UNITS)
def test_every_other_benchmark_unit_plans_to_the_fingerprint_it_had(unit):
    assert len(OTHER_UNITS) == 16
    sql = traffic.instantiate(unit, 1).sql
    cat = catalog()
    now, was = plan(sql, cat), plan(sql, cat, before=True)
    assert plan_fingerprint(now) == plan_fingerprint(was)
    assert not star_joins(now)


@pytest.mark.parametrize("number", streams.available_templates())
def test_the_census_of_the_99_templates(number):
    """The nine that engage hold a star join and plan differently; every
    other template plans to the fingerprint it had, statement by statement,
    with every rewrite pass verified (``verify_plans=per-pass``)."""
    cat = catalog()
    engaged = 0
    for stmt in template_statements(number):
        now, was = plan(stmt, cat), plan(stmt, cat, before=True)
        if star_joins(now):
            engaged += 1
            assert plan_fingerprint(now) != plan_fingerprint(was)
        else:
            assert plan_fingerprint(now) == plan_fingerprint(was)
        assert not star_joins(was)
    assert bool(engaged) == (number in ENGAGE), (number, engaged)


#: name -> (statement, the scans of the star join's build side or None)
SHAPES = {
    # query57's shape: a CTE joined to itself at rn +- 1 — three single-unit
    # stars under M:N edges
    "cte_self_join": (
        "WITH v1 AS (SELECT ss_item_sk AS k, ss_store_sk AS s, "
        "RANK() OVER (PARTITION BY ss_item_sk ORDER BY ss_ticket_number) "
        "AS rn FROM store_sales) "
        "SELECT v1.k FROM v1, v1 v1_lag, v1 v1_lead "
        "WHERE v1.k = v1_lag.k AND v1.k = v1_lead.k "
        "AND v1.rn = v1_lag.rn + 1 AND v1.rn = v1_lead.rn - 1", None),
    # the spine's star holds the dimensions, the second fact stands alone
    "a_star_and_a_bare_fact": (
        "SELECT ss_customer_sk FROM store_sales, date_dim, item, "
        "store_returns WHERE ss_sold_date_sk = d_date_sk "
        "AND ss_item_sk = i_item_sk AND d_year = 2000 "
        "AND sr_item_sk = ss_item_sk "
        "AND sr_ticket_number = ss_ticket_number", None),
    # query93's: the smaller fact holds the dimension, the spine is bare
    "a_bare_spine_and_a_star": (
        "SELECT ss_customer_sk FROM store_sales, reason, store_returns "
        "WHERE sr_reason_sk = r_reason_sk AND sr_item_sk = ss_item_sk "
        "AND sr_ticket_number = ss_ticket_number",
        ["reason", "store_returns"]),
    # no edge at all between the stars: the one JoinNode is a cross join
    "two_stars_and_no_edge": (
        "SELECT ss_customer_sk FROM store_sales, store, store_returns, "
        "reason WHERE ss_store_sk = s_store_sk "
        "AND sr_reason_sk = r_reason_sk", ["reason", "store_returns"]),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_which_shapes_keep_the_order_they_had(shape):
    sql, build = SHAPES[shape]
    cat = catalog()
    now, was = plan(sql, cat), plan(sql, cat, before=True)
    if build is None:
        assert plan_fingerprint(now) == plan_fingerprint(was)
        assert not star_joins(now)
        return
    (star,) = star_joins(now)
    assert scans(star.right) == build
    assert "store_sales" in scans(star.left)
    assert len(star.left_keys) == (0 if star.kind == "cross" else 2)


# -- query72 at SF0.01 against SQLite ---------------------------------------

Q72_TABLES = ("catalog_sales", "inventory", "warehouse", "item",
              "customer_demographics", "household_demographics", "date_dim",
              "promotion", "catalog_returns")


@pytest.fixture(scope="module")
def sf001(tmp_path_factory):
    """(query72's nine tables at SF0.01 as Arrow tables, the same rows in an
    in-memory SQLite database), generated and loaded once."""
    import sqlite3

    import pyarrow as pa
    import pyarrow.csv as pa_csv

    from nds_tpu import datagen
    data = str(tmp_path_factory.mktemp("join_stars") / "d")
    datagen.generate_data_local(data, 0.01, parallel=2, overwrite=True)
    tables, conn = {}, sqlite3.connect(":memory:")
    for name in Q72_TABLES:
        schema = get_schemas(True)[name].arrow_schema(use_decimal=False)
        names = [f.name for f in schema]
        tdir = os.path.join(data, name)
        t = tables[name] = pa.concat_tables([pa_csv.read_csv(
            os.path.join(tdir, part),
            read_options=pa_csv.ReadOptions(column_names=names),
            parse_options=pa_csv.ParseOptions(delimiter="|"),
            convert_options=pa_csv.ConvertOptions(
                column_types={f.name: f.type for f in schema},
                null_values=[""], strings_can_be_null=True,
                include_columns=names))
            for part in sorted(os.listdir(tdir))])
        conn.execute(f"CREATE TABLE {name} "
                     f"({', '.join(chr(34) + c + chr(34) for c in names)})")
        # a date goes in as its ISO text, which is what the oracle's
        # dialect translation compares and adds days to
        cols = [t.column(c).cast(pa.string()).to_pylist()
                if pa.types.is_date(t.schema.field(c).type)
                else t.column(c).to_pylist() for c in names]
        conn.executemany(f"INSERT INTO {name} VALUES "
                         f"({','.join('?' * len(names))})", zip(*cols))
    # without an index SQLite nested-loops catalog_sales x inventory
    for table, key in (("inventory", "inv_item_sk"),
                       ("customer_demographics", "cd_demo_sk"),
                       ("date_dim", "d_date_sk")):
        conn.execute(f"CREATE INDEX ix_{table} ON {table}({key})")
    conn.commit()
    return tables, conn


@pytest.mark.parametrize("year,bp,ms", [(2001, "501-1000", "D"),
                                        (1999, ">10000", "M"),
                                        (2000, "Unknown", "S")])
def test_query72_answers_as_sqlite_does_at_sf001(sf001, year, bp, ms):
    """The benchmark's plan (SF1's row counts claimed as estimates, so
    ``inventory`` is the spine and the ``catalog_sales`` star the build
    side) over SF0.01's rows, numpy and jax, against an independent
    engine."""
    from sqlite_oracle import normalize_rows, sort_rows, to_sqlite_sql

    from nds_tpu import validate
    from nds_tpu.engine import Session
    sql = query72_sql().replace("2001", str(year)) \
        .replace("'501-1000'", f"'{bp}'").replace("= 'D'", f"= '{ms}'")
    assert sql != query72_sql() or year == 2001
    tables, conn = sf001
    s = Session()
    for name, t in tables.items():
        s.register_arrow(name, t, est_rows=SF1_ROWS[name])
    planned = Planner(s._catalog()).plan_query(parse_sql(sql))
    (star,) = star_joins(planned)
    assert "inventory" in scans(star.left)
    assert "catalog_sales" in scans(star.right)
    want = sort_rows(normalize_rows(
        conn.execute(to_sqlite_sql(sql)).fetchall()))
    assert want, "the draw answers nothing at SF0.01"
    for backend in ("numpy", "jax"):
        got = s.sql(sql, backend=backend)
        rows = sort_rows(normalize_rows(got.to_pylist()))
        assert len(rows) == len(want), backend
        for a, b in zip(want, rows):
            assert validate.row_equal(a, b, "query72", list(got.names))
        assert s.last_fallbacks == []
