"""Device-resident columnar data for the JAX execution backend.

Static-shape discipline (the XLA contract): every table lives in a padded
buffer of `capacity` rows with an `alive` row mask; relational ops never
change capacity mid-kernel, so each kernel compiles once per shape bucket.
Strings are dictionary codes (int32) on device; dictionaries stay on the
host and string compute happens on the dictionary (trace-time LUTs).

This is the TPU analog of the reference's cuDF columns on GPU (reference
nds/nds_transcode.py + RAPIDS plugin do columnar compute on device; here
the columnar compute is XLA programs over padded arrays).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..column import Column, Table, is_dec, phys_np

_NULL_CODE = -1


# Below this row count, capacities are powers of two (few program shapes,
# compile-cache friendly). Above it, gather/sort cost scales with CAP and a
# 2x step overshoots the actual row count by 1.5x on average (PERF.md r5
# headroom #2), so the ladder gains 3*2^(k-1) midpoints — 4M, 6M, 8M, 12M,
# 16M, 24M... — bounding overshoot at 1.5x for a bounded set of extra
# program shapes. Midpoints keep every power-of-two divisor up to 2^(k-1),
# so mesh sharding (capacity % mesh.size == 0) is unaffected.
CAP_LADDER_MIN = 4 << 20


def bucket(n: int, minimum: int = 8) -> int:
    """Round a row count up to the capacity ladder: powers of two, plus
    3*2^(k-1) midpoints above CAP_LADDER_MIN rows."""
    c = max(int(n), minimum)
    p = 1 << (c - 1).bit_length()
    if p > CAP_LADDER_MIN:
        mid = 3 * (p >> 2)          # 0.75 * p, the step between p/2 and p
        if c <= mid:
            return mid
    return p


def phys_dtype(logical: str):
    x64 = jax.config.read("jax_enable_x64")
    if is_dec(logical):
        # scaled-int decimal: exact under x64 (TPU S64 is emulated dual-i32
        # — adds/compares, no MXU needed); i32 without x64 bounds SF (the
        # bench path keeps decimal_physical="f64" there)
        return jnp.int64 if x64 else jnp.int32
    return {
        "int": jnp.int64 if x64 else jnp.int32,
        "float": jnp.float64 if x64 else jnp.float32,
        "bool": jnp.bool_,
        "date": jnp.int32,
        "str": jnp.int32,
    }[logical]


@dataclass
class DCol:
    """A device column: padded values + always-materialized validity mask.

    Invariant: slots that are null (or dead rows) hold canonical zeros so
    grouping/sorting kernels see deterministic payloads.

    `codebook` (encoded execution): when set, `data` holds int32 CODES
    indexing this host-side SORTED array of engine-unit values (int/date/
    decN columns dictionary-encoded on the wire). The sorted order makes
    codes order-isomorphic to values, so filters, join keys, group keys and
    sorts run directly on the codes; `decode_col` materializes values only
    at arithmetic/aggregate/output sites (the generalization of the
    narrow-lane `widen_col` deferral from width to encoding). Null slots
    hold code 0 with valid=False, exactly like plain columns hold value 0.
    """
    dtype: str                 # logical: int | float | bool | date | str
    data: jax.Array
    valid: jax.Array           # bool, same length
    dictionary: Optional[np.ndarray] = None  # host object array for "str"
    parts: Optional[tuple] = None  # compound string: tuple[DCol] (lazy concat)
    codebook: Optional[np.ndarray] = None  # sorted engine-unit values

    def __len__(self) -> int:
        return int(self.data.shape[0])

    def canon(self) -> "DCol":
        zero = jnp.zeros((), dtype=self.data.dtype)
        return replace(self, data=jnp.where(self.valid, self.data, zero))


@dataclass
class DTable:
    names: list[str]
    cols: list[DCol]
    alive: jax.Array           # bool row mask, length == capacity

    @property
    def capacity(self) -> int:
        return int(self.alive.shape[0])

    def count(self) -> jax.Array:
        return jnp.sum(self.alive.astype(jnp.int32))


# -- pytree registration ------------------------------------------------------
# DCol/DTable flow through jax.jit as arguments and results of compiled whole
# -plan programs (executor.CompiledQuery). Dictionaries are host-side objects:
# they ride in aux_data, hashable by identity (scan caches keep them stable
# across calls, so jit cache keys match).

class _ById:
    """Identity-hashed wrapper so host objects can sit in pytree aux_data."""
    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self):
        return id(self.obj)

    def __eq__(self, other):
        return isinstance(other, _ById) and other.obj is self.obj


class _ByIds:
    """Element-identity-hashed wrapper for a TUPLE of host objects.

    PackedTable aux carries per-column host arrays (dictionaries,
    codebooks) in a tuple rebuilt on every pack; hashing the TUPLE by
    identity (_ById) made every morsel a fresh jit cache key — the
    compiled per-morsel program re-traced morsel after morsel even though
    the actual host objects (None slots, group-stable codebooks) never
    changed. Hashing by the ELEMENT identities keeps one cache entry per
    actual layout. The wrapper keeps the objects referenced, so their ids
    cannot be recycled while a cache key is alive."""
    __slots__ = ("objs", "_ids")

    def __init__(self, objs):
        self.objs = tuple(objs) if objs is not None else None
        self._ids = None if self.objs is None else \
            tuple(id(o) for o in self.objs)

    def __hash__(self):
        return hash(self._ids)

    def __eq__(self, other):
        return isinstance(other, _ByIds) and other._ids == self._ids

    @property
    def obj(self):
        return self.objs


def _dcol_flatten(c: DCol):
    return (c.data, c.valid, c.parts), (c.dtype, _ById(c.dictionary),
                                        _ById(c.codebook))


def _dcol_unflatten(aux, children):
    data, valid, parts = children
    return DCol(aux[0], data, valid, aux[1].obj, parts, aux[2].obj)


def _dtable_flatten(t: DTable):
    return (t.cols, t.alive), tuple(t.names)


def _dtable_unflatten(aux, children):
    cols, alive = children
    return DTable(list(aux), cols, alive)


jax.tree_util.register_pytree_node(DCol, _dcol_flatten, _dcol_unflatten)
jax.tree_util.register_pytree_node(DTable, _dtable_flatten, _dtable_unflatten)


# -- host <-> device bridging ------------------------------------------------

def _mem_leaves(dt) -> list:
    """[(id, nbytes)] of a pytree's device-array leaves — the unit the
    device-memory watermark accountant (obs/profile.DEVICE_MEM) tracks.
    Identity-keyed so add/free stay balanced even when the same buffer
    flows through several caches."""
    return [(id(leaf), int(leaf.size) * leaf.dtype.itemsize)
            for leaf in jax.tree_util.tree_leaves(dt)
            if hasattr(leaf, "size") and hasattr(leaf, "dtype")]


def to_device(table: Table, capacity: Optional[int] = None,
              device=None) -> DTable:
    from ...obs.profile import DEVICE_MEM
    from ...obs.trace import TRACER
    from ...resilience import FAULTS
    FAULTS.fire("device.put")
    n = table.num_rows
    cap = capacity if capacity is not None else bucket(n)
    with TRACER.span("upload", cat="upload", rows=n,
                     cols=len(table.columns), capacity=cap):
        out = _to_device(table, n, cap, device)
    DEVICE_MEM.add(_mem_leaves(out))
    return out


def _to_device(table: Table, n: int, cap: int, device) -> DTable:

    def put(arr):
        return jnp.asarray(arr) if device is None \
            else jax.device_put(arr, device)

    cols = []
    for c in table.columns:
        data = np.asarray(c.data)
        dt = phys_dtype(c.dtype)
        buf = np.zeros(cap, dtype=np.dtype(dt))
        v = np.zeros(cap, dtype=bool)
        v[:n] = c.validity
        buf[:n] = np.where(c.validity, data, 0)
        if c.dtype == "str":
            # canonical null slot for codes is 0 (valid=False marks them)
            buf[:n] = np.where(c.validity & (data >= 0), data, 0)
        cols.append(DCol(c.dtype, put(buf), put(v), c.dictionary))
    alive = np.zeros(cap, dtype=bool)
    alive[:n] = True
    return DTable(list(table.names), cols, put(alive))


# -- narrow-lane packed layout ------------------------------------------------
# Per-column physical lane on the host->device wire. The device unpacks lazily
# (slice + bitcast + widen fuse into the compiled program), so the wire
# width and the device compute width are decoupled:
#
#   lane   wire bytes/row   device array      legal for
#   "b1"   1/8 (bit-packed) bool              bool
#   "u8"   1                int32             int, decN, date, str
#   "u16"  2                int32             int, decN, date, str
#   "u32"  4                int32             int, decN  (values < 2^31)
#   "i32"  4                int32             int, decN, date, str
#   "i64"  8                int64             int, decN       (x64 only)
#   "f32"  4                float32 (bitcast) float           (no-x64 tier)
#   "f64"  8                float64 (bitcast) float           (x64 only)
#
# Narrow unsigned lanes require non-negative values; every lane's value
# bounds are in _LANE_BOUNDS and packing VERIFIES the data fits (a lane
# too narrow for its column is a hard error, not silent truncation).
# Unpack targets are always SIGNED (i32/i64) so downstream sort/compare/
# negate kernels never meet unsigned wraparound; int columns whose range
# fits 32 bits execute on i32 device arrays — on chips that emulate S64
# as dual u32, filters/join keys/group keys over such columns run at half
# the gather/sort cost ("encoded execution"). 64-bit widening happens
# only at arithmetic/aggregation sites (see jexprs.widen_col callers).

_LANE_WIRE = {"b1": 0, "u8": 1, "u16": 2, "u32": 4, "i32": 4,
              "i64": 8, "f32": 4, "f64": 8}   # b1: special-cased, cap/8 B

# inclusive [lo, hi] value bounds per integer lane. i32 excludes INT32_MIN:
# descending sort negates key lanes in place and -INT32_MIN would wrap,
# breaking on/off bit-identity for that (pathological) value.
_LANE_BOUNDS = {
    "u8": (0, (1 << 8) - 1),
    "u16": (0, (1 << 16) - 1),
    "u32": (0, (1 << 31) - 1),
    "i32": (-(1 << 31) + 1, (1 << 31) - 1),
    "i64": (-(1 << 63), (1 << 63) - 1),
}

_LANE_NP = {"u8": np.uint8, "u16": np.uint16, "u32": np.uint32,
            "i32": np.int32, "i64": np.int64, "f32": np.float32,
            "f64": np.float64}


def lane_legal(lane: str, dtype: str) -> bool:
    """May a column of logical `dtype` ride this lane at all? (Static
    dtype-level legality; value-range legality is checked against stats by
    the verifier and against the actual data by pack_table.)"""
    if dtype == "float":
        return lane in ("f32", "f64")   # f32 = the no-x64 physical tier
    if dtype == "bool":
        return lane == "b1"
    if dtype in ("date", "str"):
        return lane in ("u8", "u16", "i32")
    if dtype == "int" or is_dec(dtype):
        return lane in ("u8", "u16", "u32", "i32", "i64")
    return False


def _lane_rows_bytes(lane: str, cap: int) -> int:
    if lane == "b1":
        return (cap + 7) // 8
    return _LANE_WIRE[lane] * cap


def lane_bytes(lanes: tuple, cap: int) -> int:
    """Total wire bytes of a packed table: per-column data sections plus
    (ncols + 1) bit-packed validity sections (last = alive mask)."""
    return sum(_lane_rows_bytes(ln, cap) for ln in lanes) + \
        (len(lanes) + 1) * ((cap + 7) // 8)


def _narrow_int_lane(lo: int, hi: int) -> str:
    if lo >= 0:
        for lane in ("u8", "u16", "u32"):
            if hi <= _LANE_BOUNDS[lane][1]:
                return lane
    if lo >= _LANE_BOUNDS["i32"][0] and hi <= _LANE_BOUNDS["i32"][1]:
        return "i32"
    return "i64"


def plan_lanes(dtypes: list, stats: Optional[list] = None,
               dict_sizes: Optional[list] = None,
               narrow: bool = True) -> Optional[tuple]:
    """Choose a per-column lane spec from logical dtypes + optional value
    stats. stats[i] is (min, max) in ENGINE units (scaled ints for decN,
    epoch days for date) or None (unknown -> widest legal lane, always
    safe); dict_sizes[i] is the dictionary cardinality for "str" columns.

    narrow=False restores the legacy wide layout (int/dec wire int64,
    date/str wire int32, floats f64; bool/str columns unpackable -> None,
    the per-column to_device fallback; requires x64 like the old int64
    carrier did) — the --no_narrow_lanes contract. Without x64, wide
    integer/float tiers are i32/f32 (the physical dtypes that mode runs
    anyway), so narrow packing works on the no-x64 tier too.

    Returns None when some column cannot pack at all."""
    x64 = jax.config.read("jax_enable_x64")
    if not narrow and not x64:
        return None
    wide_int = "i64" if x64 else "i32"
    lanes = []
    for i, dt in enumerate(dtypes):
        st = stats[i] if stats is not None else None
        if dt == "float":
            lanes.append("f64" if x64 else "f32")
        elif dt == "bool":
            if not narrow:
                return None
            lanes.append("b1")
        elif dt == "str":
            if not narrow:
                return None
            ds = dict_sizes[i] if dict_sizes is not None else None
            if ds is None:
                lanes.append("i32")
            elif ds <= _LANE_BOUNDS["u8"][1] + 1:
                lanes.append("u8")
            elif ds <= _LANE_BOUNDS["u16"][1] + 1:
                lanes.append("u16")
            else:
                lanes.append("i32")
        elif dt == "date":
            if not narrow or st is None:
                lanes.append("i32")
            else:
                lo, hi = int(st[0]), int(st[1])
                lane = _narrow_int_lane(lo, hi)
                lanes.append(lane if lane in ("u8", "u16") else "i32")
        elif dt == "int" or is_dec(dt):
            if not narrow or st is None:
                lanes.append(wide_int)
            else:
                lane = _narrow_int_lane(int(st[0]), int(st[1]))
                # no-x64 tier: values fit 32 bits by config contract
                lanes.append("i32" if lane == "i64" and not x64 else lane)
        else:
            return None
    return tuple(lanes)


class LaneOverflowError(ValueError):
    """A column's values do not fit its declared lane (stats drift or a
    rewrite bug) — surfaced loudly instead of wrapping silently."""


class EncodingOverflowError(ValueError):
    """A column's data violates its declared encoding spec — a value not in
    the planned dictionary, or more runs than the planned run capacity.
    Encoding specs are proven against recorded table stats (the verifier's
    "encoding" findings), so this means stats drift or a planner bug, and
    it surfaces loudly instead of shipping a wrong morsel."""


# -- encoded execution: per-column wire encodings -----------------------------
# The narrow-lane machinery generalized from *width* to *encoding*: a packed
# column may additionally ride one of
#
#   enc              wire layout (data section)             device view
#   "plain"          lane bytes * cap (the lane table)      values
#   ("dict", card)   CODE-lane bytes * cap; the sorted      i32 codes +
#                    value dictionary (codebook) stays      host codebook
#                    host-side, uploaded once per group     (DCol.codebook)
#   ("rle", runs)    value-lane bytes * runs_cap + i32      values (expanded
#                    run lengths * runs_cap                 on device)
#
# Encodings are chosen STATICALLY per scan group from per-table stats
# (cardinality for dict, total run count for rle — Session.column_enc_stats)
# so every morsel of a pass shares one compiled layout. Dictionary codebooks
# are SORTED, making codes order-isomorphic to values: execution stays on
# codes through filters/joins/group-bys/sorts and decodes per-site via
# decode_col. RLE expands at unpack (jnp.repeat with a static total), so it
# is purely wire compression — the unpacked arrays are bit-identical to the
# plain lane's. `runs` is the table-wide run-count BOUND: any contiguous
# morsel window holds at most that many runs, so the per-morsel run
# capacity derived from it can never overflow while the stats hold.

def _runs_cap(runs_bound: int, cap: int) -> int:
    """Static per-morsel run capacity for an RLE column: the table-wide
    bound (+1 for the capacity-pad run) bucketed, never above cap (every
    row its own run is always representable)."""
    return min(bucket(max(int(runs_bound) + 1, 8)), cap)


def enc_rows_bytes(lane: str, enc, cap: int) -> int:
    """Wire bytes of one column's data section under its encoding."""
    if isinstance(enc, tuple) and enc[0] == "rle":
        rc = _runs_cap(enc[1], cap)
        return _LANE_WIRE[lane] * rc + 4 * rc      # values + i32 lengths
    return _lane_rows_bytes(lane, cap)             # plain / dict codes


def _code_lane(card: int) -> Optional[str]:
    if card <= _LANE_BOUNDS["u8"][1] + 1:
        return "u8"
    if card <= _LANE_BOUNDS["u16"][1] + 1:
        return "u16"
    return None


def plan_encodings(dtypes: list, lanes: tuple, enc_stats: list,
                   cap_rows: int) -> Optional[tuple]:
    """Choose per-column encodings for a scan group from cardinality/run
    stats. `lanes` is the plan_lanes value-lane spec; `enc_stats[i]` is
    {"distinct": sorted np engine-unit array or None, "runs": int or None}
    or None (no stats -> plain, always safe). Returns
    (encs, wire_lanes, codebooks) — wire_lanes replaces dict columns' value
    lane with their code lane — or None when every column stays plain."""
    encs: list = []
    out_lanes: list = []
    books: list = []
    cap = bucket(max(int(cap_rows), 8))
    any_enc = False
    for dt, lane, st in zip(dtypes, lanes, enc_stats or [None] * len(lanes)):
        choice = ("plain", lane, None)
        if st and lane not in ("b1",) and dt not in ("str", "bool"):
            width = _LANE_WIRE[lane]
            best = width * cap                      # plain cost to beat
            dv = st.get("distinct")
            if dv is not None and dt != "float":
                dv = np.asarray(dv)
                book = dv.astype(np.int64 if lane == "i64" else np.int32)
                if len(book) == 0:
                    book = np.zeros(1, dtype=book.dtype)
                clane = _code_lane(len(book))
                if clane is not None and _LANE_WIRE[clane] < width:
                    cost = _LANE_WIRE[clane] * cap
                    if cost < best:
                        best = cost
                        choice = (("dict", len(book)), clane, book)
            runs = st.get("runs")
            if runs is not None:
                cost = enc_rows_bytes(lane, ("rle", int(runs)), cap)
                # rle must beat both plain and the dict candidate by 2x:
                # marginal savings don't earn the expansion pass
                if cost * 2 <= best:
                    choice = (("rle", int(runs)), lane, None)
        encs.append(choice[0])
        out_lanes.append(choice[1])
        books.append(choice[2])
        any_enc = any_enc or choice[0] != "plain"
    if not any_enc:
        return None
    return tuple(encs), tuple(out_lanes), tuple(books)


def enc_lane_bytes(lanes: tuple, cap: int, encs: Optional[tuple]) -> int:
    """lane_bytes generalized over encodings (None = all plain)."""
    if encs is None:
        return lane_bytes(lanes, cap)
    return sum(enc_rows_bytes(ln, e, cap) for ln, e in zip(lanes, encs)) + \
        (len(lanes) + 1) * ((cap + 7) // 8)


# -- device codebook cache (satellite: once-per-group dictionary upload) ------
# decode sites gather through the device copy of a group's codebook; the
# codebook object is morsel-invariant for a scan group, so the upload
# happens once and every later decode (and every later morsel's eager
# re-record) reuses it — counted via obs/metrics dict_uploads_saved.

_BOOK_CACHE: dict = {}          # id(book) -> (pinned np array, device array)
_BOOK_CACHE_MAX = 256

# decode-site observability: how many decode_col calls actually decoded,
# and how many column slots they materialized — the "execution stays on
# codes" evidence (a group key that never decodes at morsel scale shows up
# as decode_rows << morsels * capacity)
_DECODE_STATS = {"sites": 0, "rows": 0}


def decode_stats() -> dict:
    return dict(_DECODE_STATS)


def _codebook_device(book: np.ndarray) -> jax.Array:
    from ...obs.profile import DEVICE_MEM
    ent = _BOOK_CACHE.get(id(book))
    if ent is not None and ent[0] is book:
        from ...obs import metrics as _metrics
        _metrics.DICT_UPLOADS_SAVED.inc()
        return ent[1]
    if len(_BOOK_CACHE) >= _BOOK_CACHE_MAX:
        DEVICE_MEM.free([pair for e in _BOOK_CACHE.values()
                         for pair in _mem_leaves(e[1])])
        _BOOK_CACHE.clear()
    # the upload must happen OUTSIDE any live trace: a traced constant
    # would be a tracer, and caching a tracer across programs leaks it
    with jax.ensure_compile_time_eval():
        dev = jnp.asarray(book)
    _BOOK_CACHE[id(book)] = (book, dev)
    DEVICE_MEM.add(_mem_leaves(dev))
    return dev


def decode_col(c: DCol) -> DCol:
    """Materialize an encoded column's values: codes gather through the
    device-resident codebook (null/dead slots stay canonical zeros). The
    per-site decode seam — callers are the sites that genuinely need
    values: arithmetic/aggregate arguments, cross-codebook comparisons,
    and output materialization. Everything else (filters via trace-time
    literal remap, join keys, group keys, sorts) runs on the codes."""
    if c.codebook is None:
        return c
    book = _codebook_device(c.codebook)
    safe = jnp.clip(c.data, 0, book.shape[0] - 1)
    data = jnp.where(c.valid, book[safe], jnp.zeros((), book.dtype))
    _DECODE_STATS["sites"] += 1
    _DECODE_STATS["rows"] += int(c.data.shape[0])
    from ...obs import metrics as _metrics
    _metrics.DECODE_SITES.inc()
    return replace(c, data=data, codebook=None)


def encode_against(book: np.ndarray, c: DCol) -> jax.Array:
    """Map a PLAIN column's values into another column's code space: the
    exact code where the value is in the codebook, -1 (matches no code)
    otherwise. Join keys use this to keep the big encoded side on its i32
    codes — the small plain side pays one searchsorted instead of the big
    side paying a per-row decode."""
    dev = _codebook_device(book)
    vals = c.canon().data
    ct = jnp.promote_types(dev.dtype, vals.dtype)
    bw = dev.astype(ct)
    vw = vals.astype(ct)
    idx = jnp.clip(jnp.searchsorted(bw, vw), 0,
                   dev.shape[0] - 1).astype(jnp.int32)
    return jnp.where(bw[idx] == vw, idx, jnp.full((), -1, jnp.int32))


@dataclass
class PackedTable:
    """A columnar table packed for ONE-transfer upload: every column
    payload and every validity mask rides in a single contiguous uint8
    buffer. Column sections use per-column narrow lanes (see the lane table
    above); validity masks (plus the alive mask, last) are bit-packed at
    1 bit/row. Every host->device transfer has a fixed cost besides its
    bytes — per column a streamed morsel paid ~2*ncols of them per
    dispatch; packed it pays 1. Columns unpack INSIDE the traced
    program as zero-copy views (slice/bitcast/bit-unpack fuse into the
    compiled plan). The lane spec is pytree aux_data, so compiled-program
    cache keys include the physical layout and a lane change can never
    replay a stale program. Requires x64 (i64/f64 lanes)."""
    names: list[str]
    dtypes: list[str]           # logical dtypes
    lanes: tuple                # per-column WIRE lane tags (code lane for
    #                             dict-encoded columns), see _LANE_WIRE
    cap: int                    # padded row capacity
    data: jax.Array             # uint8[enc_lane_bytes(lanes, cap, encs)]
    dictionaries: tuple = ()    # host dictionaries for "str" columns
    # per-column encoding tags ("plain" | ("dict", card) | ("rle", runs
    # bound)); () = all plain (the pre-encoding layout, byte-identical)
    encs: tuple = ()
    codebooks: tuple = ()       # host sorted value arrays for dict columns

    @property
    def capacity(self) -> int:
        return self.cap

    def col_enc(self, i: int):
        return self.encs[i] if self.encs else "plain"


def _packed_flatten(p: PackedTable):
    return (p.data,), (tuple(p.names), tuple(p.dtypes), p.lanes, p.cap,
                       _ByIds(p.dictionaries), p.encs, _ByIds(p.codebooks))


def _packed_unflatten(aux, children):
    return PackedTable(list(aux[0]), list(aux[1]), aux[2], aux[3],
                       children[0], aux[4].obj, aux[5], aux[6].obj)


jax.tree_util.register_pytree_node(PackedTable, _packed_flatten,
                                   _packed_unflatten)


def pack_table(table: Table, capacity: Optional[int] = None,
               lanes: Optional[tuple] = None, encs: Optional[tuple] = None,
               codebooks: Optional[tuple] = None) -> Optional[PackedTable]:
    """Host-side packing for upload; None if the table can't pack under the
    given lane spec (default: the legacy wide layout, which rejects
    strings/bools exactly like the pre-lane int64 carrier did).

    `lanes` is the STATIC per-column lane spec: streaming computes it once
    per scan group from table-wide column stats and passes it for every
    morsel, so morsel widths never drift mid-stream (a width change would
    be a different compiled program). Values are VERIFIED against the lane
    bounds — stats drift raises LaneOverflowError instead of wrapping."""
    if lanes is None:
        lanes = plan_lanes([c.dtype for c in table.columns], narrow=False)
        if lanes is None:
            return None
    if not jax.config.read("jax_enable_x64") and \
            any(ln in ("i64", "f64") for ln in lanes):
        return None     # 64-bit lanes unrepresentable on the no-x64 tier
    if len(lanes) != len(table.columns):
        raise ValueError(f"{len(lanes)} lanes for {len(table.columns)} "
                         "columns")
    n = table.num_rows
    cap = capacity if capacity is not None else bucket(n)
    from ...obs.trace import TRACER
    from ...resilience import FAULTS
    # the packed-upload twin of to_device's fault point: streamed morsels
    # ride this path exclusively, so chaos campaigns arming device.put
    # must reach them too (one firing per staged morsel upload)
    FAULTS.fire("device.put")
    with TRACER.span("lane.pack", cat="upload", rows=n,
                     cols=len(table.columns), capacity=cap):
        out = _pack_table(table, lanes, n, cap, encs, codebooks)
    from ...obs.profile import DEVICE_MEM
    DEVICE_MEM.add(_mem_leaves(out))
    return out


def _pack_table(table: Table, lanes: tuple, n: int, cap: int,
                encs: Optional[tuple] = None,
                codebooks: Optional[tuple] = None) -> PackedTable:
    payload, dicts = _pack_payload(table, lanes, n, cap, encs, codebooks)
    return PackedTable(list(table.names), [c.dtype for c in table.columns],
                       tuple(lanes), cap, jnp.asarray(payload), tuple(dicts),
                       tuple(encs) if encs else (),
                       tuple(codebooks) if codebooks else ())


def _pack_col_rle(name: str, buf: np.ndarray, lane: str, runs_bound: int,
                  cap: int) -> list[np.ndarray]:
    """(values, run-lengths) sections for one canonicalized cap-padded
    column buffer. Run lengths sum to cap exactly (the capacity pad rides
    the trailing run), so device expansion reconstructs the buffer
    bit-for-bit; more runs than the planned capacity is stats drift."""
    rc = _runs_cap(runs_bound, cap)
    if cap == 0:
        return [np.zeros(0, dtype=_LANE_NP[lane]).view(np.uint8),
                np.zeros(0, dtype=np.int32).view(np.uint8)]
    starts = np.concatenate(
        [[0], np.flatnonzero(buf[1:] != buf[:-1]) + 1])
    if len(starts) > rc:
        raise EncodingOverflowError(
            f"column {name!r}: {len(starts)} runs overflow the planned "
            f"run capacity {rc} (runs bound {runs_bound})")
    lengths = np.diff(np.concatenate([starts, [cap]]))
    vbuf = np.zeros(rc, dtype=_LANE_NP[lane])
    lbuf = np.zeros(rc, dtype=np.int32)
    vbuf[:len(starts)] = buf[starts]
    lbuf[:len(starts)] = lengths
    return [vbuf.view(np.uint8), lbuf.view(np.uint8)]


def _dict_codes(name: str, data: np.ndarray, v: np.ndarray, n: int,
                book: np.ndarray) -> np.ndarray:
    """Row codes into a sorted codebook; a VALID value missing from the
    book is stats drift (null/dead slots ride code 0 like plain zeros)."""
    idx = np.searchsorted(book, data)
    safe = np.clip(idx, 0, max(len(book) - 1, 0))
    ok = (idx < len(book)) & (book[safe] == data) if len(book) else \
        np.zeros(len(data), dtype=bool)
    bad = ~ok & v
    if n and bad[:n].any():
        missing = data[:n][bad[:n]][0]
        raise EncodingOverflowError(
            f"column {name!r}: value {int(missing)} not in the planned "
            f"dictionary (card {len(book)})")
    return np.where(v, safe, 0).astype(np.int64)


def _pack_payload(table: Table, lanes: tuple, n: int, cap: int,
                  encs: Optional[tuple] = None,
                  codebooks: Optional[tuple] = None) -> tuple[np.ndarray,
                                                              list]:
    """Host-side packed payload bytes (the PackedTable wire format) WITHOUT
    the device upload: sharded morsel staging packs one payload per replica
    row block and uploads the concatenation in a single row-sharded
    device_put (shard_exec.stage_sharded)."""
    parts: list[np.ndarray] = []
    vparts: list[np.ndarray] = []
    dicts = []
    for ci, (c, lane) in enumerate(zip(table.columns, lanes)):
        enc = encs[ci] if encs else "plain"
        dict_enc = isinstance(enc, tuple) and enc[0] == "dict"
        if not dict_enc and not lane_legal(lane, c.dtype):
            raise LaneOverflowError(
                f"column {table.names[ci]!r}: lane {lane!r} illegal for "
                f"dtype {c.dtype!r}")
        v = c.validity
        data = np.asarray(c.data)
        if c.dtype == "str":
            # canonical null slot for codes is 0 (valid=False marks them)
            data = np.where(v & (data >= 0), data, 0)
            dicts.append(c.dictionary)
        else:
            dicts.append(None)
            data = np.where(v, data, np.zeros((), dtype=data.dtype))
        if dict_enc:
            # data section holds codebook codes on the (narrower) code lane
            data = _dict_codes(table.names[ci], data, v, n, codebooks[ci])
        if lane == "b1":
            bits = np.zeros(cap, dtype=bool)
            bits[:n] = data.astype(bool)
            parts.append(np.packbits(bits, bitorder="little"))
        else:
            lo, hi = _LANE_BOUNDS.get(lane, (None, None))
            if lo is not None and n and data.size:
                dmin, dmax = int(data[:n].min()), int(data[:n].max())
                if dmin < lo or dmax > hi:
                    raise LaneOverflowError(
                        f"column {table.names[ci]!r} values "
                        f"[{dmin}, {dmax}] overflow lane {lane!r}")
            buf = np.zeros(cap, dtype=_LANE_NP[lane])
            buf[:n] = data
            if isinstance(enc, tuple) and enc[0] == "rle":
                parts.extend(_pack_col_rle(table.names[ci], buf, lane,
                                           enc[1], cap))
            else:
                parts.append(buf.view(np.uint8))
        vbits = np.zeros(cap, dtype=bool)
        vbits[:n] = v
        vparts.append(np.packbits(vbits, bitorder="little"))
    alive = np.zeros(cap, dtype=bool)
    alive[:n] = True
    vparts.append(np.packbits(alive, bitorder="little"))
    payload = np.concatenate(parts + vparts) if parts + vparts else \
        np.zeros(0, dtype=np.uint8)
    return payload, dicts


def _unpack_bits(seg: jax.Array, cap: int) -> jax.Array:
    """Bit-packed bytes (little bit order) -> bool[cap], 32-bit WORDS at a
    time: XLA:TPU compiles the (n, 8) -> (8n) collapse ``jnp.unpackbits``
    lowers to in time linear in n — 21 s at 1M rows, 98 s at 4M, per mask
    (v5e, PERF.md PR 21) — and the (n, 32) -> (32n) one in about a second.
    The bits are the same."""
    from jax import lax

    pad = -seg.shape[0] % 4
    if pad:
        seg = jnp.concatenate([seg, jnp.zeros(pad, jnp.uint8)])
    words = lax.bitcast_convert_type(
        seg.reshape(seg.shape[0] // 4, 4), jnp.uint32)
    shifts = lax.broadcasted_iota(jnp.uint32, (words.shape[0], 32), 1)
    bits = (words[:, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(-1)[:cap].astype(bool)


def _unpack_lane(seg: jax.Array, lane: str, cap: int) -> jax.Array:
    """Bytes -> device array for one column (traced or concrete); narrow
    unsigned lanes widen to SIGNED i32 so downstream kernels never meet
    unsigned wraparound."""
    from jax import lax

    if lane == "b1":
        return _unpack_bits(seg, cap)
    if lane == "u8":
        return seg.astype(jnp.int32)
    width = _LANE_WIRE[lane]
    carrier = {"u16": jnp.uint16, "u32": jnp.uint32, "i32": jnp.int32,
               "i64": jnp.int64, "f32": jnp.float32,
               "f64": jnp.float64}[lane]
    out = lax.bitcast_convert_type(seg.reshape(cap, width), carrier)
    if lane in ("u16", "u32"):
        out = out.astype(jnp.int32)     # u32 bound is 2^31-1: no overflow
    return out


def unpack_table(p: PackedTable) -> DTable:
    """Traced (or concrete) unpacking back into per-column device arrays:
    each column is a zero-copy byte-slice view of the single uploaded
    buffer, bitcast to its lane carrier and widened to its signed device
    dtype — all of which fuses into the consuming compiled program.
    Dict-encoded columns come up as i32 codes with the host codebook
    attached (execution stays on codes; decode_col materializes values
    per-site); RLE columns expand to row-aligned values right here (a
    static-shape jnp.repeat that fuses like the bitcasts do)."""
    from jax import lax

    vbytes = (p.cap + 7) // 8
    cols = []
    off = 0
    encs = p.encs or ("plain",) * len(p.dtypes)
    voff = sum(enc_rows_bytes(ln, e, p.cap)
               for ln, e in zip(p.lanes, encs))
    dicts = p.dictionaries or (None,) * len(p.dtypes)
    books = p.codebooks or (None,) * len(p.dtypes)
    for dtype, lane, dc, enc, book in zip(p.dtypes, p.lanes, dicts, encs,
                                          books):
        sz = enc_rows_bytes(lane, enc, p.cap)
        seg = p.data[off:off + sz]
        if isinstance(enc, tuple) and enc[0] == "rle":
            rc = _runs_cap(enc[1], p.cap)
            vsz = _LANE_WIRE[lane] * rc
            vals = _unpack_lane(seg[:vsz], lane, rc)
            lens = lax.bitcast_convert_type(
                seg[vsz:vsz + 4 * rc].reshape(rc, 4), jnp.int32)
            d = jnp.repeat(vals, lens, total_repeat_length=p.cap)
            book = None
        else:
            d = _unpack_lane(seg, lane, p.cap)
            if not (isinstance(enc, tuple) and enc[0] == "dict"):
                book = None
        valid = _unpack_bits(p.data[voff:voff + vbytes], p.cap)
        cols.append(DCol(dtype, d, valid, dc, codebook=book))
        off += sz
        voff += vbytes
    alive = _unpack_bits(p.data[voff:voff + vbytes], p.cap)
    return DTable(list(p.names), cols, alive)


def widen_col(c: DCol) -> DCol:
    """Physical-width view of a column: an encoded column decodes
    (decode_col) and a narrow-lane device array widens to the logical
    physical dtype. Callers are the sites that genuinely need 64-bit
    arithmetic — aggregate/window arguments and decimal rescaling —
    everything else (filters, join keys, group keys, sorts) runs on the
    narrow encoding."""
    c = decode_col(c)
    if c.dtype in ("bool", "str", "date", "float"):
        return c
    pd = phys_dtype(c.dtype)
    if c.data.dtype == pd or not jnp.issubdtype(c.data.dtype, jnp.integer):
        return c
    return replace(c, data=c.data.astype(pd))


def device_bytes(dt: "Optional[DTable | PackedTable]") -> int:
    """Device bytes held by a table (DTable or PackedTable — any pytree of
    device arrays). Streaming uses it to account uploaded morsel bytes
    (last_exec_stats.bytes_uploaded): upload volume is the cost the
    shared scan divides by the branch count."""
    if dt is None:
        return 0
    return sum(int(leaf.size) * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(dt)
               if hasattr(leaf, "size") and hasattr(leaf, "dtype"))


def free_dtable(dt: "Optional[DTable | PackedTable]") -> None:
    """Explicitly release a cached entry's device buffers (DTable or
    PackedTable — any pytree of device arrays).

    Dropping the Python reference leaves freeing to gc timing — streaming
    loops that rebind a morsel buffer hundreds of times must free eagerly
    or accumulate the whole scan in device memory."""
    if dt is None:
        return
    from ...obs.profile import DEVICE_MEM
    DEVICE_MEM.free(_mem_leaves(dt))
    for leaf in jax.tree_util.tree_leaves(dt):
        if hasattr(leaf, "delete"):
            try:
                leaf.delete()
            except Exception:
                pass


def to_host(dt: DTable, count: Optional[int] = None) -> Table:
    """Materialize a device table back into a host Table (compacted).

    All buffers come back in ONE device_get: each D2H transfer is a
    synchronisation with the device, so per-column np.asarray would
    multiply that latency by the column count.
    """
    dt = jax.device_get(dt)
    alive = np.asarray(dt.alive)
    idx = np.flatnonzero(alive)
    if count is not None:
        idx = idx[:count]
    cols = []
    for c in dt.cols:
        c = _flatten_compound(c)
        data = np.asarray(c.data)[idx]
        valid = np.asarray(c.valid)[idx]
        if c.codebook is not None:
            # output materialization IS a decode site: codes -> values
            book = c.codebook
            safe = np.clip(data, 0, max(len(book) - 1, 0))
            data = np.where(valid, book[safe] if len(book) else 0, 0)
        if c.dtype == "str":
            data = np.where(valid, data, _NULL_CODE).astype(np.int32)
        host_dtype = phys_np(c.dtype)
        cols.append(Column(c.dtype, data.astype(host_dtype),
                           None if bool(valid.all()) else valid, c.dictionary))
    return Table(list(dt.names), cols)


def _flatten_compound(c: DCol) -> DCol:
    """Materialize a lazy-concat compound string column into a real dictionary.

    Concrete path: string appends run once per *distinct* part-code tuple
    (rows deduplicated over stacked codes). Traced path (inside a compiled
    plan): the output dictionary must be data-INdependent, so it becomes the
    mixed-radix cross product of the part dictionaries (+ an empty-string
    slot per part for null/invalid codes) and row codes are computed on
    device — sized like the id-column dictionary for the typical
    literal||column||literal concat.
    """
    if c.parts is None:
        return c
    if any(isinstance(p.data, jax.core.Tracer) for p in c.parts) or \
            isinstance(c.valid, jax.core.Tracer):
        return _flatten_compound_traced(c)
    code_mat = np.stack([np.where(np.asarray(p.valid), np.asarray(p.data), -1)
                         for p in c.parts], axis=1)
    uniq_rows, inverse = np.unique(code_mat, axis=0, return_inverse=True)
    joined = np.full(len(uniq_rows), "", dtype=object)
    for j, p in enumerate(c.parts):
        d = p.dictionary if p.dictionary is not None else np.empty(0, dtype=object)
        codes = uniq_rows[:, j]
        safe = np.clip(codes, 0, max(len(d) - 1, 0))
        vals = np.where(codes >= 0,
                        d[safe] if len(d) else "", "")
        joined = np.asarray([a + b for a, b in zip(joined, vals)], dtype=object)
    uniq, remap = np.unique(joined.astype(str), return_inverse=True)
    codes = remap.astype(np.int32)[inverse]
    return DCol("str", jnp.asarray(codes), c.valid, uniq.astype(object))


def _flatten_compound_traced(c: DCol) -> DCol:
    """Trace-safe compound flatten: cross-product dictionary, device codes."""
    dicts = []
    for p in c.parts:
        d = p.dictionary if p.dictionary is not None \
            else np.empty(0, dtype=object)
        # slot len(d) holds "" for null/invalid part codes
        dicts.append(np.concatenate([d.astype(object),
                                     np.asarray([""], dtype=object)]))
    total = 1
    for d in dicts:
        total *= len(d)
    if total > (1 << 20):
        raise NotImplementedError(
            f"compound string cross dictionary too large ({total})")
    # mixed-radix joined dictionary, last part fastest-varying
    joined = np.asarray([""], dtype=object)
    for d in dicts:
        joined = np.asarray([a + b for a in joined for b in d], dtype=object)
    code = jnp.zeros(c.parts[0].data.shape, jnp.int32)
    for p, d in zip(c.parts, dicts):
        n = len(d)
        eff = jnp.where(p.valid & (p.data >= 0),
                        jnp.clip(p.data, 0, n - 2 if n > 1 else 0),
                        n - 1).astype(jnp.int32)
        code = code * n + eff
    return DCol("str", code, c.valid, joined)


def string_rank_maps(dictionary: Optional[np.ndarray]
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Host LUTs for a string dictionary: (code -> dense lexicographic rank,
    dense rank -> representative code).

    Equal strings get EQUAL ranks (dictionaries from compound cross products
    may contain duplicates; distinct ranks would break equality compares),
    so mapping an aggregated rank back to a code must go through the
    rank->code table — NOT through argsort position.
    """
    if dictionary is None or len(dictionary) == 0:
        return np.zeros(1, dtype=np.int32), np.zeros(1, dtype=np.int32)
    vals = dictionary.astype(str)
    order = np.argsort(vals, kind="stable")
    svals = vals[order]
    dense = np.cumsum(np.concatenate(
        [[0], (svals[1:] != svals[:-1]).astype(np.int32)])).astype(np.int32)
    ranks = np.empty(len(vals), dtype=np.int32)
    ranks[order] = dense
    rank_to_code = np.zeros(int(dense[-1]) + 1, dtype=np.int32)
    # reversed assignment => the FIRST occurrence in sorted order wins
    rank_to_code[dense[::-1]] = order[::-1].astype(np.int32)
    return ranks, rank_to_code


def string_rank_lut(dictionary: Optional[np.ndarray]) -> np.ndarray:
    """Host LUT: dictionary code -> dense lexicographic rank."""
    return string_rank_maps(dictionary)[0]


def rank_key(c: DCol) -> jax.Array:
    """Device array usable as a grouping/ordering key for any logical dtype."""
    c = _flatten_compound(c)
    if c.dtype == "str":
        lut = jnp.asarray(string_rank_lut(c.dictionary))
        safe = jnp.clip(c.data, 0, lut.shape[0] - 1)
        return jnp.where(c.valid, lut[safe], 0)
    if c.dtype == "bool":
        return jnp.where(c.valid, c.data.astype(jnp.int32), 0)
    return jnp.where(c.valid, c.data, jnp.zeros((), dtype=c.data.dtype))
