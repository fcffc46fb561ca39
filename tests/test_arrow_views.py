"""`arrow_bridge.from_arrow_column` by buffer view: a fixed-width Arrow
column (integers, date32, exact-i64 decimal128) becomes an engine column
from the value buffer Arrow holds, at the array's offset, with the slots
under nulls zeroed. The reference is `to_pylist()` with `Decimal.scaleb`,
independent of the conversion under test."""
import datetime
import decimal
import gc

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.engine import arrow_bridge
from nds_tpu.engine.arrow_bridge import from_arrow, from_arrow_column
from nds_tpu.obs.metrics import METRICS

D = decimal.Decimal
EPOCH = datetime.date(1970, 1, 1)
N = 131_072 + 40            # rows before slicing: offsets up to 131,071
I64_MAX = 2 ** 63 - 1


def reference(arr, scale=None):
    """(values with nulls -> 0, validity) from python objects alone."""
    vals, valid = [], []
    for v in arr.to_pylist():
        valid.append(v is not None)
        if v is None:
            vals.append(0)
        elif isinstance(v, D):
            vals.append(int(v.scaleb(scale)))
        elif isinstance(v, datetime.date):
            vals.append((v - EPOCH).days)
        else:
            vals.append(v)
    return vals, valid


def check(col, arr, dtype, np_dtype, scale=None):
    vals, valid = reference(arr, scale)
    assert col.dtype == dtype
    assert col.data.dtype == np_dtype
    assert col.data.flags.c_contiguous
    assert col.data.tolist() == vals
    if all(valid):
        assert col.valid is None
    else:
        assert col.valid.dtype == np.bool_
        assert col.valid.tolist() == valid


def null_mask(n, nulls, rng):
    return {"none": None, "some": rng.random(n) < 0.3,
            "all": np.ones(n, dtype=bool)}[nulls]


def make_ints(t, n, nulls, rng, edges=()):
    info = np.iinfo(t.to_pandas_dtype())
    vals = rng.integers(info.min, info.max, n, dtype=info.dtype,
                        endpoint=True)
    vals[:len(edges)] = edges
    return pa.array(vals, type=t, mask=null_mask(n, nulls, rng))


def make_decimals(t, n, nulls, rng, edges=()):
    digits = min(t.precision, 18)
    ints = rng.integers(-(10 ** digits - 1), 10 ** digits - 1, n,
                        endpoint=True).tolist()
    ints[:len(edges)] = edges
    mask = null_mask(n, nulls, rng)
    return pa.array([None if mask is not None and mask[i]
                     else D(v).scaleb(-t.scale) for i, v in enumerate(ints)],
                    type=t)


def make_dates(n, nulls, rng):
    days = rng.integers(-30_000, 60_000, n).astype(np.int32)
    return pa.array(days, type=pa.int32(),
                    mask=null_mask(n, nulls, rng)).cast(pa.date32())


OFFSETS = [0, 1, 7, 8, 9, 131_071]
NULLS = ["none", "some", "all"]


@pytest.mark.parametrize("offset", OFFSETS)
@pytest.mark.parametrize("nulls", NULLS)
@pytest.mark.parametrize("prec,scale,edges", [
    (7, 2, ()),
    (18, 0, (10 ** 18 - 1, -(10 ** 18 - 1))),
    (20, 2, (I64_MAX, -I64_MAX - 1)),       # wide by type, fits by value
], ids=["dec7_2", "dec18_0", "dec20_2"])
def test_decimal128_view(prec, scale, edges, nulls, offset):
    rng = np.random.default_rng(prec * 100 + offset)
    # short arrays for the small offsets, the long one for the last
    n = N if offset > 9 else 64
    arr = make_decimals(pa.decimal128(prec, scale), n, nulls, rng, edges)
    sliced = arr.slice(offset)
    assert sliced.offset == offset
    col = from_arrow_column(sliced, dec_as_int=True)
    check(col, sliced, f"dec{scale}", np.int64, scale)


@pytest.mark.parametrize("offset", OFFSETS)
@pytest.mark.parametrize("nulls", NULLS)
@pytest.mark.parametrize("t", [pa.int8(), pa.int16(), pa.int32(),
                               pa.int64(), pa.uint8(), pa.uint32()], ids=str)
def test_integer_view(t, nulls, offset):
    rng = np.random.default_rng(offset + 7)
    n = N if offset > 9 else 64
    sliced = make_ints(t, n, nulls, rng).slice(offset)
    check(from_arrow_column(sliced), sliced, "int", np.int64)


@pytest.mark.parametrize("offset", OFFSETS)
@pytest.mark.parametrize("nulls", NULLS)
def test_date32_view(nulls, offset):
    rng = np.random.default_rng(offset + 11)
    n = N if offset > 9 else 64
    sliced = make_dates(n, nulls, rng).slice(offset)
    check(from_arrow_column(sliced), sliced, "date", np.int32)


@pytest.mark.parametrize("edge", [I64_MAX, -I64_MAX, -I64_MAX - 1])
def test_int64_beyond_2_53_beside_a_null(edge):
    # to_numpy on an integer array with nulls goes through float64, which
    # rounds these; the view reads the 64 bits that are there
    arr = pa.array([edge, None, edge - 1 if edge > 0 else edge + 1],
                   type=pa.int64())
    col = from_arrow_column(arr)
    check(col, arr, "int", np.int64)


@pytest.mark.parametrize("prec,scale,big", [
    (38, 0, 2 ** 63), (38, 0, -(2 ** 63) - 1), (20, 0, 10 ** 20 - 1),
    (38, 0, 2 ** 64 + 5),       # low word small and positive, high word 1
    (38, 0, -(2 ** 64)),        # low word 0, high word -1
], ids=["i64max+1", "i64min-1", "dec20_max", "hi1", "hi-1"])
def test_wide_decimal_that_does_not_fit_raises(prec, scale, big):
    arr = pa.array([D(1), D(big), None], type=pa.decimal128(prec, scale))
    with pytest.raises(OverflowError):
        from_arrow_column(arr, dec_as_int=True)
    # ... in any chunk
    chunked = pa.chunked_array([arr.slice(0, 1), arr.slice(1)])
    with pytest.raises(OverflowError):
        from_arrow_column(chunked, dec_as_int=True)


@pytest.mark.parametrize("offset", [0, 1, 9])
def test_wide_decimal_out_of_range_under_a_null_is_no_overflow(offset):
    # a slot under a null is undefined in Arrow: whatever it holds, it is
    # neither a value nor an overflow
    n = 24
    words = np.zeros(2 * n, dtype="<i8")
    words[0::2] = np.arange(n)
    words[2 * 10] = 5
    words[2 * 10 + 1] = 7                   # slot 10: about 7 * 2^64
    bits = np.ones(n, dtype=np.uint8)
    bits[10] = 0
    arr = pa.Array.from_buffers(
        pa.decimal128(38, 0), n,
        [pa.py_buffer(np.packbits(bits, bitorder="little").tobytes()),
         pa.py_buffer(words.tobytes())], null_count=1).slice(offset)
    col = from_arrow_column(arr, dec_as_int=True)
    check(col, arr, "dec0", np.int64, 0)
    assert col.data[10 - offset] == 0
    with pytest.raises(OverflowError):      # the same slot, valid
        from_arrow_column(pa.Array.from_buffers(
            pa.decimal128(38, 0), n, [None, pa.py_buffer(words.tobytes())]),
            dec_as_int=True)


@pytest.mark.parametrize("nulls", NULLS)
@pytest.mark.parametrize("kind", ["dec", "int32", "int64", "date"])
def test_chunked_array_of_several_chunks(kind, nulls):
    # what `pa.concat_tables` over `Table.slice`s hands `from_arrow`: chunks
    # at their own offsets, some without nulls, one empty
    rng = np.random.default_rng(5)
    make = {"dec": lambda: make_decimals(pa.decimal128(7, 2), 200, nulls, rng),
            "int32": lambda: make_ints(pa.int32(), 200, nulls, rng),
            "int64": lambda: make_ints(pa.int64(), 200, nulls, rng),
            "date": lambda: make_dates(200, nulls, rng)}[kind]
    a, b = make(), make()
    clean = make_dates(50, "none", rng) if kind == "date" else \
        make_decimals(pa.decimal128(7, 2), 50, "none", rng) \
        if kind == "dec" else make_ints(a.type, 50, "none", rng)
    chunked = pa.chunked_array([a.slice(3, 100), b.slice(0, 0), clean,
                                b.slice(9), a.slice(131, 8)])
    assert chunked.num_chunks == 5
    col = from_arrow_column(chunked, dec_as_int=True)
    dtype, np_dtype = {"dec": ("dec2", np.int64), "int32": ("int", np.int64),
                       "int64": ("int", np.int64),
                       "date": ("date", np.int32)}[kind]
    check(col, chunked, dtype, np_dtype, 2)


@pytest.mark.parametrize("t,dtype,np_dtype", [
    (pa.decimal128(7, 2), "dec2", np.int64),
    (pa.decimal128(38, 4), "dec4", np.int64),
    (pa.int32(), "int", np.int64), (pa.int64(), "int", np.int64),
    (pa.date32(), "date", np.int32)], ids=str)
@pytest.mark.parametrize("chunked", [False, True], ids=["array", "chunked"])
def test_length_zero(t, dtype, np_dtype, chunked):
    arr = pa.chunked_array([], type=t) if chunked else pa.array([], type=t)
    col = from_arrow_column(arr, dec_as_int=True)
    assert (col.dtype, col.data.dtype, len(col), col.valid) == \
        (dtype, np_dtype, 0, None)
    # and an empty slice of an array that has values
    full = pa.array([D(1), D(2), D(3)], type=t) if pa.types.is_decimal(t) \
        else pa.array([1, 2, 3], type=pa.int32()).cast(t)
    col = from_arrow_column(full.slice(2, 0), dec_as_int=True)
    assert (col.data.dtype, len(col), col.valid) == (np_dtype, 0, None)


def test_arrays_outlive_the_arrow_table():
    # an int64 column without nulls stays a view of Arrow's buffer: the
    # engine column has to keep that buffer alive by itself
    vals = np.arange(100_000, dtype=np.int64)
    table = pa.table({"k": pa.array(vals),
                      "d": pa.array(vals, type=pa.int64())
                      .cast(pa.decimal128(19, 0)),
                      "n": pa.array(vals, mask=vals % 3 == 0)})
    out = from_arrow(table.slice(5), dec_as_int=True)
    assert not out.columns[0].data.flags.owndata
    del table
    gc.collect()
    junk = [np.full(100_000, -1, dtype=np.int64) for _ in range(8)]
    assert out.columns[0].data.tolist() == vals[5:].tolist()
    assert out.columns[1].data.tolist() == vals[5:].tolist()
    assert out.columns[2].data.tolist() == \
        np.where(vals % 3 == 0, 0, vals)[5:].tolist()
    assert out.columns[2].valid.tolist() == (vals % 3 != 0)[5:].tolist()
    del junk


def test_float_mapped_decimals_strings_and_bools_keep_their_paths():
    """The view takes only what it covers: the float mapping of decimals
    rounds arrow's own way (`cast(float64)`), strings stay dictionary
    codes, bools stay bit-unpacked by arrow — and each is counted."""
    dec = pa.array([D("1.10"), None, D("-99999.99")], type=pa.decimal128(7, 2))
    table = pa.table({
        "d": dec, "s": pa.array(["a", None, "b"]),
        "b": pa.array([True, None, False]),
        "f": pa.array([1.5, None, -2.0]), "i": pa.array([1, None, 3])})
    before = METRICS.snapshot()
    out = from_arrow(table)                 # dec_as_int False
    delta = METRICS.delta(before)
    assert delta["arrow_view_columns"] == 1             # "i"
    assert delta["arrow_fallback_columns"] == 4
    d, s, b, f, i = out.columns
    assert d.dtype == "float" and d.data.tolist() == \
        dec.cast(pa.float64()).fill_null(0.0).to_pylist()
    assert s.dtype == "str" and s.data.dtype == np.int32 \
        and s.data.tolist()[1] == -1
    assert b.dtype == "bool" and b.data.dtype == np.bool_
    assert f.dtype == "float" and f.data.tolist() == [1.5, 0.0, -2.0]
    assert i.data.tolist() == [1, 0, 3]
    for c in out.columns:
        assert c.valid.tolist() == [True, False, True]


def test_other_decimal_widths_take_arrows_cast_and_are_counted_fallback():
    arr = pa.array([D("12.50"), None, D("-0.01")], type=pa.decimal256(40, 2))
    before = METRICS.snapshot()
    col = from_arrow_column(arr, dec_as_int=True)
    delta = METRICS.delta(before)
    check(col, arr, "dec2", np.int64, 2)
    assert delta.get("arrow_view_columns", 0) == 0
    assert delta["arrow_fallback_columns"] == 1


def test_from_arrow_counts_and_reports_to_its_span():
    class Sp:
        attrs = {}

        def set(self, **kw):
            self.attrs.update(kw)

    table = pa.table({"k": pa.array([1, 2], type=pa.int32()),
                      "d": pa.array([D("1.00"), None],
                                    type=pa.decimal128(7, 2)),
                      "t": pa.array([1, 2], type=pa.int32()).cast(pa.date32()),
                      "s": pa.array(["x", "y"])})
    sp = Sp()
    before = METRICS.snapshot()
    from_arrow(table, dec_as_int=True, span=sp)
    delta = METRICS.delta(before)
    assert sp.attrs == {"viewed": 3, "fallback": 1}
    assert (delta["arrow_view_columns"], delta["arrow_fallback_columns"]) \
        == (3, 1)
    # a system table's poll converts uncounted: it may move no counter
    before = METRICS.snapshot()
    from_arrow(table, dec_as_int=True, counted=False)
    assert not {k for k in METRICS.delta(before) if k.startswith("arrow_")}


def test_enc_stats_still_count_runs_over_zeroed_nulls():
    # `column_enc_stat` counts RLE runs over nulls -> 0: the view has to
    # zero whatever the slot under a null holds
    words = np.zeros(16, dtype="<i8")
    words[0::2] = [4, 4, 99, 4, 0, 77, 0, 4]      # 99, 77 sit under nulls
    bits = np.array([1, 1, 0, 1, 1, 0, 1, 1], dtype=np.uint8)
    arr = pa.Array.from_buffers(
        pa.decimal128(7, 2), 8,
        [pa.py_buffer(np.packbits(bits, bitorder="little").tobytes()),
         pa.py_buffer(words.tobytes())], null_count=2)
    st = arrow_bridge.column_enc_stat(arr, dec_as_int=True)
    assert st["runs"] == 5                  # 4 4 | 0 | 4 | 0 0 0 | 4
    assert st["distinct"].tolist() == [0, 4]


def test_date64_is_refused_by_name():
    # it never converted (arrow has no date64 -> int32 cast): say so
    arr = pa.array([datetime.date(2020, 1, 1), None], type=pa.date64())
    with pytest.raises(TypeError, match="date64"):
        from_arrow_column(arr)
