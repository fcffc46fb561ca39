"""Encoded execution end-to-end (round 13).

The narrow-lane machinery generalized from width to ENCODING
(device.plan_encodings): low-cardinality int/date/decimal columns upload as
dictionary CODES on u8/u16 lanes plus a once-per-group host codebook, and
clustered columns upload as (value, run-length) pairs expanded on device.
Execution stays on codes where legality allows — equality/IN filters remap
literals through the sorted codebook at trace time, join and group keys
factorize codes directly, sorts ride the order-preserving dictionary — and
device.decode_col materializes values only at arithmetic/aggregate/output
sites. Exactness is pinned by a property round trip over dtypes x
encodings x validity patterns, on/off bit-identity differentials on
streamed shapes (plus a numpy oracle and a slow-marked SF0.01 SQLite
slice), verifier "encoding" findings, and a sharded (mesh_shards=2)
encoded round trip."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session, arrow_bridge
from nds_tpu.engine.column import Column, Table
from nds_tpu.engine.jax_backend.device import (
    EncodingOverflowError, device_bytes, enc_lane_bytes, lane_bytes,
    pack_table, plan_encodings, plan_lanes, to_device, to_host,
    unpack_table)

N_FACT, N_DIM = 50_000, 300
CHUNK = 4_096


def _col(dtype, data, valid=None, dictionary=None):
    return Column.from_values(dtype, np.asarray(data), valid, dictionary)


def _validity(pattern, n, rng):
    if pattern == "none_null":
        return None
    if pattern == "all_null":
        return np.zeros(n, dtype=bool)
    return rng.random(n) < 0.7


# ---------------------------------------------------------------------------
# pack/unpack round trip: dtypes x encodings x validity patterns
# ---------------------------------------------------------------------------

# (name, dtype, value domain, expected enc kind)
_DICT_CASES = [
    ("int_wide_lowcard", "int", np.arange(0, 3_000_000, 9973), "dict"),
    ("dec2_lowcard", "dec2", np.arange(-500_000, 500_000, 7919), "dict"),
    ("date_lowcard", "date", np.arange(2_450_000, 2_453_000, 7), "dict"),
    ("int_single_value", "int", np.asarray([1_234_567]), "dict"),
    # max cardinality for a u8 code lane: exactly 256 distinct values
    ("int_u8_boundary", "int", np.arange(0, 256_000, 1000), "dict"),
]


@pytest.mark.parametrize("pattern", ["none_null", "mixed", "all_null"])
@pytest.mark.parametrize("name,dtype,domain,kind", _DICT_CASES,
                         ids=[c[0] for c in _DICT_CASES])
def test_roundtrip_dict(name, dtype, domain, kind, pattern):
    rng = np.random.default_rng(hash((name, pattern)) % 2 ** 31)
    n = 700
    data = rng.choice(domain, n)
    valid = _validity(pattern, n, rng)
    t = Table([name], [_col(dtype, data, valid)])
    lanes = plan_lanes([dtype], [(int(domain.min()), int(domain.max()))])
    st = arrow_bridge.column_enc_stat_values(
        np.asarray(t.columns[0].data), t.columns[0].validity)
    st["runs"] = None    # isolate the dict candidate (degenerate shapes —
    #                      single value, all-null — would prefer rle)
    planned = plan_encodings([dtype], lanes, [st], 1024)
    assert planned is not None
    encs, wire_lanes, books = planned
    assert encs[0][0] == kind
    packed = pack_table(t, capacity=1024, lanes=wire_lanes, encs=encs,
                        codebooks=books)
    dt = unpack_table(packed)
    assert (dt.cols[0].codebook is not None) == (kind == "dict")
    got = to_host(dt)
    want = to_host(to_device(t, capacity=1024))
    np.testing.assert_array_equal(np.asarray(got.columns[0].data),
                                  np.asarray(want.columns[0].data))
    np.testing.assert_array_equal(got.columns[0].validity,
                                  want.columns[0].validity)


@pytest.mark.parametrize("pattern", ["none_null", "mixed", "all_null"])
@pytest.mark.parametrize("shape", ["sorted_runs", "single_run", "run_len_1"])
def test_roundtrip_rle(shape, pattern):
    rng = np.random.default_rng(hash((shape, pattern)) % 2 ** 31)
    n = 700
    if shape == "sorted_runs":
        data = np.sort(rng.integers(0, 40, n)) * 1_000_003
    elif shape == "single_run":
        data = np.full(n, 77)
    else:  # run_len_1: every row its own run (worst case, still exact)
        data = np.arange(n) * 3 + 1
    valid = _validity(pattern, n, rng)
    t = Table(["r"], [_col("int", data, valid)])
    lanes = plan_lanes(["int"], [(int(data.min()), int(data.max()))])
    st = arrow_bridge.column_enc_stat_values(
        np.asarray(t.columns[0].data), t.columns[0].validity)
    st["distinct"] = None          # force the rle candidate
    encs = (("rle", st["runs"]),)
    packed = pack_table(t, capacity=1024, lanes=lanes, encs=encs,
                        codebooks=(None,))
    got = to_host(unpack_table(packed))
    want = to_host(to_device(t, capacity=1024))
    np.testing.assert_array_equal(np.asarray(got.columns[0].data),
                                  np.asarray(want.columns[0].data))
    np.testing.assert_array_equal(got.columns[0].validity,
                                  want.columns[0].validity)


def test_encoding_overflow_rejects():
    """Data violating the declared encoding spec must fail LOUDLY: a value
    outside the dictionary or more runs than planned would otherwise ship
    a silently wrong morsel."""
    book = np.asarray([10, 20, 30], dtype=np.int32)
    bad = Table(["x"], [_col("int", np.asarray([10, 25]))])
    with pytest.raises(EncodingOverflowError):
        pack_table(bad, capacity=8, lanes=("u8",), encs=(("dict", 3),),
                   codebooks=(book,))
    # nulls ride code 0 without being dictionary members
    nullish = Table(["x"], [_col("int", np.asarray([10, 99]),
                                 np.asarray([True, False]))])
    assert pack_table(nullish, capacity=8, lanes=("u8",),
                      encs=(("dict", 3),), codebooks=(book,)) is not None
    alternating = Table(["x"], [_col("int", np.arange(100) % 7)])
    with pytest.raises(EncodingOverflowError):
        pack_table(alternating, capacity=128, lanes=("u8",),
                   encs=(("rle", 4),), codebooks=(None,))


def test_plan_encodings_selection():
    """Selection policy: dict only when the code lane is strictly narrower
    than the value lane, rle only on a >= 2x data-section win, plain
    otherwise; no stats -> None (all plain, always safe)."""
    # wide-range low-cardinality int: i32 value lane -> u16 codes
    st = {"distinct": np.arange(0, 3_000_000, 9973), "runs": None}
    encs, wlanes, books = plan_encodings(["int"], ("u32",), [st], 4096)
    assert encs[0][0] == "dict" and wlanes == ("u16",)
    assert books[0].dtype == np.int32
    # u8-range column: codes cannot beat the u8 value lane -> plain
    assert plan_encodings(["int"], ("u8",),
                          [{"distinct": np.arange(200), "runs": None}],
                          4096) is None
    # clustered column: few runs -> rle on the value lane
    encs, wlanes, _ = plan_encodings(["int"], ("u32",),
                                     [{"distinct": None, "runs": 50}], 4096)
    assert encs[0][0] == "rle" and wlanes == ("u32",)
    # run-length-1 data: run count ~ rows -> no win -> plain
    assert plan_encodings(["int"], ("u32",),
                          [{"distinct": None, "runs": 4096}], 4096) is None
    assert plan_encodings(["int"], ("u32",), [None], 4096) is None
    # bytes accounting covers the encoded sections
    encs, wlanes, books = plan_encodings(["int"], ("u32",), [st], 4096)
    p = pack_table(Table(["x"], [_col("int", st["distinct"][:100])]),
                   capacity=4096, lanes=wlanes, encs=encs, codebooks=books)
    assert device_bytes(p) == enc_lane_bytes(wlanes, 4096, encs) \
        < lane_bytes(("u32",), 4096)


# ---------------------------------------------------------------------------
# streamed differentials: encoded on vs off bit-identical, fewer bytes,
# joins/group-bys demonstrably on codes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def bench_shape(tmp_path_factory):
    """An NDS-fact shape stressing every encoding: a wide-range
    low-cardinality join key (dict), a scaled-decimal-like price (dict), a
    date-clustered column (rle), a quantity already on u8 (plain), and a
    float payload."""
    tmp = tmp_path_factory.mktemp("encoded_exec")
    rng = np.random.default_rng(29)
    days = np.sort(rng.integers(2_450_000, 2_450_200, N_FACT))
    fk_domain = np.arange(0, 3_000_000, 9973)       # 301 wide-spread keys
    price_domain = np.arange(100, 3_000_000, 7919)  # 379 distinct prices
    qty = rng.integers(1, 100, N_FACT).astype(object)
    qty[rng.random(N_FACT) < 0.05] = None
    fact = pa.table({
        "fk": pa.array(rng.choice(fk_domain, N_FACT), type=pa.int64()),
        "qty": pa.array(list(qty), type=pa.int32()),
        "price": pa.array(rng.choice(price_domain, N_FACT),
                          type=pa.int64()),
        "day": pa.array(days, type=pa.int64()),
        "f": pa.array(np.round(rng.uniform(0, 10, N_FACT), 3)),
    })
    path = os.path.join(str(tmp), "fact.parquet")
    pq.write_table(fact, path, row_group_size=8192)
    dim = pa.table({"dk": pa.array(fk_domain, type=pa.int64()),
                    "grp": pa.array((np.arange(len(fk_domain)) % 13)
                                    .astype(np.int32))})
    return {"fact_path": path, "dim": dim}


Q_BENCH = """
SELECT d.grp, SUM(f.qty) AS s, COUNT(*) AS c, MIN(f.price) AS mp,
       MAX(f.day) AS md, SUM(f.f) AS sf
FROM fact f JOIN dim d ON f.fk = d.dk
WHERE f.day < 2450150 AND f.price > 5000
GROUP BY d.grp ORDER BY d.grp
"""


def _session(data, encoded, **kw):
    cfg = EngineConfig(out_of_core=True, chunk_rows=CHUNK,
                       out_of_core_min_rows=10_000, encoded_exec=encoded,
                       **kw)
    s = Session(cfg)
    s.register_parquet("fact", data["fact_path"])
    s.register_arrow("dim", data["dim"])
    return s


def rows_of(t):
    return [tuple(r) for r in t.to_pylist()]


def test_encoded_off_bit_identical_and_bytes(bench_shape):
    """Acceptance: default (encoded) vs --no_encoded_exec results are
    BIT-IDENTICAL while bytes_uploaded drops >= 1.5x, with per-pass plan
    verification (incl. encoding/stats legality) green in both modes."""
    s_on = _session(bench_shape, True, verify_plans="per-pass")
    on = rows_of(s_on.sql(Q_BENCH, backend="jax"))
    st_on = dict(s_on.last_exec_stats)
    s_off = _session(bench_shape, False, verify_plans="per-pass")
    off = rows_of(s_off.sql(Q_BENCH, backend="jax"))
    st_off = dict(s_off.last_exec_stats)
    assert st_on["mode"] == st_off["mode"] == "streaming"
    assert on == off
    assert st_on["encoded_exec"] and not st_off["encoded_exec"]
    assert st_on["bytes_uploaded"] * 1.5 <= st_off["bytes_uploaded"]
    spec = st_on["enc_spec"]["fact"]
    assert spec["fk"].startswith("dict[") and spec["price"].startswith(
        "dict[")
    assert spec["day"].startswith("rle[")
    assert spec["qty"] == "plain" and spec["f"] == "plain"
    # dict columns ride their CODE lane on the wire
    assert st_on["lane_spec"]["fact"]["fk"] == "u16"
    assert st_on["enc_bytes_saved"] > 0
    assert st_off.get("enc_spec") is None
    # host-side morsel decode wall is now measurable per streamed table
    assert st_on["host_decode_ms"]["fact"] > 0
    # numpy oracle (float tolerance on the f64 sum only)
    oracle = rows_of(_session(bench_shape, True)
                     .sql(Q_BENCH, backend="numpy"))
    assert len(on) == len(oracle)
    for a, b in zip(on, oracle):
        assert a[:5] == b[:5]
        assert abs(a[5] - b[5]) <= 1e-6 * max(1.0, abs(b[5]))


def test_join_and_groupby_run_on_codes(bench_shape):
    """The decode-site evidence: the dict-encoded join key never
    materializes values at morsel scale — only the aggregate ARGUMENTS
    decode (qty/price sums at morsel capacity), so decode_rows stays a
    small multiple of the morsel cap instead of sites x morsels x cap,
    and a full replay run decodes NOTHING. The second sighting traces once
    more (its programs are sized from what the first whole pass saw) and
    decodes no more than the first; from the third on a run is pure replay."""
    s = _session(bench_shape, True)
    s.sql(Q_BENCH, backend="jax")
    st1 = dict(s.last_exec_stats)
    assert st1["decode_sites"] > 0
    # record + one jit trace: each decodes the agg args once; the fk join
    # key and day filter contribute no morsel-scale decode, so the total
    # stays bounded by (2 passes) x (agg-arg sites) x cap + group-sized
    # output decodes, far under morsels x cap
    assert st1["decode_rows"] <= 6 * CHUNK
    assert st1["morsels"] * CHUNK > 6 * CHUNK
    s.sql(Q_BENCH, backend="jax")
    st2 = dict(s.last_exec_stats)
    # one jit trace of the tight program, no record pass
    assert 0 < st2["decode_sites"] <= st1["decode_sites"]
    assert 0 < st2["decode_rows"] <= st1["decode_rows"]
    assert st2["re_records"] == 0
    s.sql(Q_BENCH, backend="jax")
    st3 = dict(s.last_exec_stats)
    assert st3["decode_sites"] == 0 and st3["decode_rows"] == 0
    assert st3["re_records"] == 0


def test_filter_literal_remap(bench_shape):
    """Equality/range/IN filters on dict-encoded columns remap literals
    into code space at trace time — including literals ABSENT from the
    dictionary (eq -> empty, ne -> all valid rows, range -> boundary)."""
    s_on = _session(bench_shape, True)
    s_off = _session(bench_shape, False)
    queries = [
        # 9973*7 is in the fk dictionary; 9974 is not
        "SELECT COUNT(*) c FROM fact WHERE fk = 69811",
        "SELECT COUNT(*) c FROM fact WHERE fk = 9974",
        "SELECT COUNT(*) c FROM fact WHERE fk <> 9974",
        "SELECT COUNT(*) c FROM fact WHERE price > 5000 AND price <= 100000",
        "SELECT COUNT(*) c FROM fact WHERE fk IN (69811, 9974, 19946)",
        "SELECT COUNT(*) c, SUM(qty) s FROM fact WHERE day >= 2450100",
    ]
    for q in queries:
        on = rows_of(s_on.sql(q, backend="jax"))
        off = rows_of(s_off.sql(q, backend="jax"))
        oracle = rows_of(s_on.sql(q, backend="numpy"))
        assert on == off == oracle, q


def test_sort_rides_order_preserving_dictionary(bench_shape):
    """ORDER BY an encoded column: the sorted codebook makes code order ==
    value order, so the streamed sort result matches the plain path."""
    q = ("SELECT price, COUNT(*) c FROM fact WHERE day < 2450100 "
         "GROUP BY price ORDER BY price DESC LIMIT 50")
    on = rows_of(_session(bench_shape, True).sql(q, backend="jax"))
    off = rows_of(_session(bench_shape, False).sql(q, backend="jax"))
    assert on == off and len(on) == 50


def test_live_toggle_invalidates_stream_cache(bench_shape):
    """encoded_exec is part of the stream-cache config fingerprint: a live
    toggle must re-derive groups/encodings/programs, not replay stale."""
    s = _session(bench_shape, True)
    a = rows_of(s.sql(Q_BENCH, backend="jax"))
    assert s.last_exec_stats["enc_spec"]
    s.config.encoded_exec = False
    b = rows_of(s.sql(Q_BENCH, backend="jax"))
    assert s.last_exec_stats.get("enc_spec") is None
    assert a == b


def test_dict_upload_cache_counts_hits(bench_shape):
    """The per-group device codebook uploads once; every later decode site
    / morsel re-record reuses it (obs/metrics dict_uploads_saved). Since
    ISSUE 38 the join on ``fk`` is direct and maps its key pair once a trace
    (the sort-based path mapped it again), so the second use of ``fk``'s
    codebook is the second sighting's trace of the tight programs."""
    from nds_tpu.obs.metrics import METRICS
    before = METRICS.snapshot()
    s = _session(bench_shape, True)
    s.sql(Q_BENCH, backend="jax")
    s.sql(Q_BENCH, backend="jax")
    after = METRICS.snapshot()
    assert after.get("dict_uploads_saved", 0) > \
        before.get("dict_uploads_saved", 0)
    assert after.get("decode_sites", 0) > before.get("decode_sites", 0)


def test_a_dimension_with_keys_the_fact_never_holds_joins_on_codes_directly(
        bench_shape):
    """The dimension's keys map into the fact key's code space; those the
    codebook lacks are all -1 there and match nothing. They stay out of the
    direct-address table (ISSUE 38: the keys' 3M span no longer sends this
    join to the sort-based path), so the replay — which sees codes where
    the record pass saw values — finds no duplicate and no morsel
    re-records."""
    extra = np.arange(5, 3_000_000, 99_991)
    dk = np.concatenate([bench_shape["dim"]["dk"].to_numpy(), extra])
    data = dict(bench_shape, dim=pa.table({
        "dk": pa.array(dk, type=pa.int64()),
        "grp": pa.array((np.arange(len(dk)) % 13).astype(np.int32))}))
    oracle = rows_of(_session(data, True).sql(Q_BENCH, backend="numpy"))
    s = _session(data, True)
    for _sighting in range(2):
        got = rows_of(s.sql(Q_BENCH, backend="jax"))
        st = dict(s.last_exec_stats)
        assert st["mode"] == "streaming" and st["re_records"] == 0
        assert st["enc_spec"]["fact"]["fk"].startswith("dict[")
        assert [r[:5] for r in got] == [r[:5] for r in oracle]
    (entry,) = s._stream_cache.values()
    assert [cq.join_paths[:2] for g in entry["gstates"] for cq in g["cqs"]] == \
        [(1, 0)]


def test_sharded_encoded_roundtrip(bench_shape):
    """mesh_shards=2: the encoded morsel payload lands row-sharded (equal
    per-replica packed blocks, codebooks shared) and stays bit-identical
    to the single-chip encoded path AND to the plain path. Integer/decimal
    partials only — float partial sums are order-sensitive across shard
    counts (the documented PR-8 restriction), so the differential query
    keeps the exact-integer shape."""
    q = ("SELECT d.grp, SUM(f.qty) s, COUNT(*) c, MIN(f.price) mp, "
         "MAX(f.day) md FROM fact f JOIN dim d ON f.fk = d.dk "
         "WHERE f.day < 2450150 GROUP BY d.grp ORDER BY d.grp")
    single = rows_of(_session(bench_shape, True).sql(q, backend="jax"))
    plain = rows_of(_session(bench_shape, False).sql(q, backend="jax"))
    s = _session(bench_shape, True, mesh_shards=2)
    sharded = rows_of(s.sql(q, backend="jax"))
    st = dict(s.last_exec_stats)
    assert st["sharded_groups"] == 1 and st["mesh_shards"] == 2
    assert st["enc_spec"]["fact"]["fk"].startswith("dict[")
    assert sharded == single == plain


# ---------------------------------------------------------------------------
# fast multi-shape differential battery (the plan-sweep complement: every
# streaming shape the planner emits — union channels, semi-join build
# sides, scalar subqueries — on/off bit-identical)
# ---------------------------------------------------------------------------

_SHAPES = [
    ("scalar_subquery",
     "SELECT COUNT(*) c FROM fact WHERE price > "
     "(SELECT AVG(price) FROM fact)"),
    ("semi_join",
     "SELECT COUNT(*) c FROM dim d WHERE d.dk IN "
     "(SELECT f.fk FROM fact f WHERE f.day < 2450100)"),
    ("case_over_encoded",
     "SELECT SUM(CASE WHEN price > 100000 THEN qty ELSE 0 END) s, "
     "MIN(day) md FROM fact"),
    ("group_by_encoded_key",
     "SELECT price, COUNT(*) c FROM fact GROUP BY price "
     "ORDER BY c DESC, price LIMIT 20"),
    ("arith_on_encoded",
     "SELECT SUM(price * qty) s, AVG(price) a FROM fact "
     "WHERE day BETWEEN 2450050 AND 2450150"),
]


@pytest.mark.parametrize("name,q", _SHAPES, ids=[s[0] for s in _SHAPES])
def test_shape_differentials(bench_shape, name, q):
    on = rows_of(_session(bench_shape, True).sql(q, backend="jax"))
    off = rows_of(_session(bench_shape, False).sql(q, backend="jax"))
    assert on == off, name


# ---------------------------------------------------------------------------
# verifier: encoding metadata legality ("encoding" findings)
# ---------------------------------------------------------------------------

def test_verifier_encoding_findings():
    from nds_tpu.engine.plan import ScanNode
    from nds_tpu.engine.verify import (check_scan_encodings, verify_plan)

    scan = ScanNode("__morsel__", ["a", "b"], lanes=("u8", "u16"),
                    encodings=(("dict", 100), ("rle", 40)),
                    out_names=["a", "b"], out_dtypes=["int", "int"])
    ok = check_scan_encodings(scan, {
        "a": {"distinct": np.arange(100), "runs": None},
        "b": {"distinct": None, "runs": 40}})
    assert ok == []
    # stats that do not cover the declared spec
    bad = check_scan_encodings(scan, {
        "a": {"distinct": np.arange(150), "runs": None},
        "b": {"distinct": None, "runs": 99}})
    assert len(bad) == 2 and all(f.kind == "encoding" for f in bad)
    # a spec with NO stats proving it is itself a finding
    unproven = check_scan_encodings(scan, {})
    assert len(unproven) == 2
    assert "no distinct-value stats" in unproven[0].message
    # static dtype/lane legality (verify_plan path): cardinality past the
    # code lane, dict on float, rle on the bit-packed bool lane
    illegal = ScanNode(
        "__morsel__", ["x", "y", "z"], lanes=("u8", "f64", "b1"),
        encodings=(("dict", 300), ("dict", 4), ("rle", 5)),
        out_names=["x", "y", "z"], out_dtypes=["int", "float", "bool"])
    findings = verify_plan(illegal)
    msgs = [f.message for f in findings if f.kind == "encoding"]
    assert any("overflows code lane" in m for m in msgs)
    assert any("illegal for dtype 'float'" in m for m in msgs)
    assert any("bit-packed bool lane" in m for m in msgs)


def test_verify_groups_rejects_lying_enc_stats(bench_shape):
    """Session-level: per-pass verification proves each group's encoding
    spec against the SAME stats source the planner used."""
    from nds_tpu.engine import streaming
    from nds_tpu.engine.verify import PlanVerifyError

    s = _session(bench_shape, True, verify_plans="per-pass")
    s.sql(Q_BENCH, backend="jax")
    ent = s._stream_cache[Q_BENCH]
    g = ent["groups"][0]
    assert g.encodings is not None
    shrunk = tuple(("dict", 2) if isinstance(e, tuple) and e[0] == "dict"
                   else e for e in g.encodings)
    streaming.set_group_encodings(g, shrunk, g.lanes, g.codebooks)
    with pytest.raises(PlanVerifyError) as exc:
        streaming.verify_groups(ent["groups"],
                                enc_stats=s.column_enc_stats)
    assert "encoded_exec" in str(exc.value)


# ---------------------------------------------------------------------------
# encoding-stats sources: arrow tables, parquet column reads, engine
# views, warehouse manifests
# ---------------------------------------------------------------------------

def test_enc_stats_sources(tmp_path):
    import decimal
    t = pa.table({
        "i": pa.array([5, 5, None, 900_000, 5], type=pa.int64()),
        "d": pa.array([10_957, 10_957, 10_958, 10_958, 10_958],
                      type=pa.date32()),
        "dec": pa.array([decimal.Decimal("1.25")] * 5,
                        type=pa.decimal128(10, 2)),
        "s": pa.array(["x"] * 5),
    })
    path = os.path.join(str(tmp_path), "t.parquet")
    pq.write_table(t, path)
    s = Session(EngineConfig(decimal_physical="i64"))
    s.register_arrow("mem", t)
    s.register_parquet("disk", path)
    for name in ("mem", "disk"):
        st = s.column_enc_stats(name, ["i", "d", "dec", "s"])
        assert list(st["i"]["distinct"]) == [5, 900_000]
        assert st["i"]["runs"] == 4          # 5,5,0(null),900000,5
        assert st["d"]["runs"] == 2
        assert list(st["dec"]["distinct"]) == [125]
        assert "s" not in st
    # re-registration invalidates the per-column cache
    s.register_arrow("mem", t.slice(0, 2))
    assert list(s.column_enc_stats("mem", ["i"])["i"]["distinct"]) == [5]
    # engine-view registrations compute from the materialized table
    view = s.sql("SELECT i FROM mem", backend="numpy")
    s.register_view("v", view)
    assert s.column_enc_stats("v", ["i"])["i"]["runs"] >= 1


def test_warehouse_manifest_enc_stats(tmp_path):
    from nds_tpu.warehouse import Warehouse

    wh = Warehouse(str(tmp_path))
    t1 = pa.table({"k": pa.array([7, 7, 7, 9], type=pa.int64()),
                   "hi": pa.array(np.arange(4) * 99991, type=pa.int64())})
    t2 = pa.table({"k": pa.array([9, 11], type=pa.int64()),
                   "hi": pa.array([5, 6], type=pa.int64())})
    wt = wh.table("demo")
    wt.create(t1, partition=False)
    wt.insert(t2, partition=False)
    rec = wt.enc_stats()
    assert len(rec) == 2
    agg = wt.column_enc_stats(wt.current_files())
    assert list(agg["k"]["distinct"]) == [7, 9, 11]
    assert agg["k"]["runs"] == 2 + 2     # per-file runs SUM (window bound)
    s = Session(EngineConfig(decimal_physical="i64"))
    wh.register_all(s)
    st = s.column_enc_stats("demo", ["k"])
    assert list(st["k"]["distinct"]) == [7, 9, 11]


# ---------------------------------------------------------------------------
# satellite: parquet dictionary pass-through (staging-thread hot loop)
# ---------------------------------------------------------------------------

def test_parquet_dictionary_passthrough(tmp_path):
    """String columns dictionary-encoded in the parquet chunks register
    with ParquetReadOptions(dictionary_columns=...): batches arrive as
    dictionary arrays and from_arrow_column passes codes through without
    re-running dictionary_encode()."""
    vals = [f"cat{i % 40}" for i in range(5000)]
    t = pa.table({"s": pa.array(vals),
                  "i": pa.array(np.arange(5000), type=pa.int64())})
    path = os.path.join(str(tmp_path), "dict.parquet")
    pq.write_table(t, path, use_dictionary=True, row_group_size=1024)
    assert arrow_bridge.parquet_dictionary_columns([path]) == ["s"]
    s = Session(EngineConfig())
    s.register_parquet("t", path)
    batch = next(iter(s._batch_sources["t"](["s"])))
    arr = batch.column(0) if hasattr(batch, "column") else batch["s"]
    assert pa.types.is_dictionary(
        arr.type if not isinstance(arr, pa.ChunkedArray) else arr.type)
    got = s.sql("SELECT s, COUNT(*) c FROM t GROUP BY s ORDER BY s",
                backend="jax")
    assert len(rows_of(got)) == 40
    # a column with dictionary disabled must NOT be forced through it
    path2 = os.path.join(str(tmp_path), "plain.parquet")
    pq.write_table(t, path2, use_dictionary=False)
    assert arrow_bridge.parquet_dictionary_columns([path2]) == []


# ---------------------------------------------------------------------------
# slow: whole-template-sweep on/off bit-identity (streamed tiny SF) and the
# SF0.01 SQLite-oracle slice (full CI test stage; tier-1 runs the fast
# differentials above)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep_sessions(tmp_path_factory):
    """Tiny-SF sessions with the streaming threshold dropped so fact scans
    actually ride the packed (encoded) morsel path — the on/off pair for
    the full template sweep."""
    from nds_tpu import datagen
    from nds_tpu.power import setup_tables
    data = str(tmp_path_factory.mktemp("enc_sweep") / "d")
    datagen.generate_data_local(data, 0.001, parallel=2, overwrite=True)
    out = {}
    for encoded in (True, False):
        s = Session(EngineConfig(encoded_exec=encoded,
                                 out_of_core_min_rows=1000,
                                 chunk_rows=4096))
        setup_tables(s, data, "csv")
        out[encoded] = s
    return out


def _template_numbers():
    from nds_tpu import streams
    return streams.available_templates()


@pytest.mark.slow
@pytest.mark.parametrize("number", _template_numbers())
def test_template_sweep_on_off_identity(sweep_sessions, number):
    """EVERY bundled template, streamed, encoded on vs off: results must be
    BIT-IDENTICAL (same rows, same order) — the template-sweep complement
    of the fast shape differentials above. Each side runs twice and the
    COMPILED steady-state results compare: cross-session program adoption
    would otherwise pit one side's eager record pass against the other's
    compiled replay, whose float expressions differ by ULPs for reasons
    independent of encoding (pre-existing, q78-class round() columns)."""
    from nds_tpu import streams
    sql = streams.instantiate(number, stream=0, rngseed=31415)
    parts = (streams.split_special_query(f"query{number}", sql)
             if number in streams.SPECIAL_TEMPLATES
             else [(f"query{number}", sql)])
    for name, part_sql in parts:
        for s in (sweep_sessions[True], sweep_sessions[False]):
            s.sql(part_sql, backend="jax", label=name)   # record/compile
        on = rows_of(sweep_sessions[True].sql(part_sql, backend="jax",
                                              label=name))
        off = rows_of(sweep_sessions[False].sql(part_sql, backend="jax",
                                                label=name))
        assert on == off, f"{name}: encoded on/off differ"

@pytest.fixture(scope="module")
def nds_env(tmp_path_factory):
    from nds_tpu import datagen
    from nds_tpu.power import setup_tables
    from sqlite_oracle import load_database
    data = str(tmp_path_factory.mktemp("encoded_nds") / "d")
    datagen.generate_data_local(data, 0.01, parallel=4, overwrite=True)
    conn = load_database(data)

    def mk(encoded):
        # stream the fact scans at SF0.01 so the encoded packed path is
        # actually exercised (the bench A/B uses the same knobs)
        s = Session(EngineConfig(encoded_exec=encoded,
                                 out_of_core_min_rows=20_000,
                                 chunk_rows=1 << 15))
        setup_tables(s, data, "csv")
        return s
    return mk, conn


@pytest.mark.slow
@pytest.mark.parametrize("number", [9, 22, 67, 95])
def test_nds_query_encoded_sqlite_differential(nds_env, number):
    from nds_tpu import streams, validate
    from sqlite_oracle import normalize_rows, sort_rows, to_sqlite_sql
    mk, conn = nds_env
    sql = streams.instantiate(number, stream=0, rngseed=778)
    name = f"query{number}"
    expected = conn.execute(to_sqlite_sql(sql)).fetchall()
    rows = {}
    for label, encoded in (("off", False), ("on", True)):
        s = mk(encoded)
        t = s.sql(sql, backend="jax", label=name)
        at = arrow_bridge.to_arrow(t)
        rows[label] = [tuple(r[c] for c in at.column_names)
                       for r in at.to_pylist()]
        names = list(t.names)
    assert rows["on"] == rows["off"], f"{name}: encoded on/off differ"
    rows_e = sort_rows(normalize_rows(expected))
    rows_a = sort_rows(normalize_rows(rows["on"]))
    assert len(rows_e) == len(rows_a), name
    for re_, ra_ in zip(rows_e, rows_a):
        assert validate.row_equal(re_, ra_, name, names), \
            f"{name}: sqlite {re_} != engine {ra_}"
