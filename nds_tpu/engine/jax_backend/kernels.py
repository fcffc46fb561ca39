"""Relational kernels as traceable JAX programs (static shapes, masked rows).

Design rules (TPU/XLA-first):
- No data-dependent shapes inside a kernel: outputs are padded to a capacity
  chosen by the caller; a row-`alive` mask carries the logical row set.
- No hashing: grouping and joins are sort-based (`lax.sort` is deterministic
  and maps well onto TPU); multi-column keys are reduced to a dense group id
  by a joint factorize, so every join/aggregate is single-int-key.
- Nulls ride as validity masks; null payload slots are canonical zeros.

These kernels are the device counterparts of engine/ops.py (the numpy oracle
backend, which mirrors what the reference gets from Spark SQL executors,
reference nds/nds_power.py:124-134).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..plan import AggSpec, SortKey, WindowFunc

_I32 = jnp.int32


def _iota(n: int) -> jax.Array:
    return jnp.arange(n, dtype=_I32)


def _scoped(fn):
    """Every public lowering below opens a named scope under its own name.

    Run a kernel entry point under ``jax.named_scope(<its name>)``:
    inside a plan program every instruction it emits then carries
    ``jit(nds_<query>_<unit>)/.../<TypeName#k>/<kernel>/...`` as its
    ``op_name``, what a profile groups device time by. A scope is a debug
    location: it changes no instruction and is stripped from the compile
    cache's key. (A new context manager per call: ``jax.named_scope`` used
    as a decorator shares one between the compile pool's threads.)"""
    name = fn.__name__

    @functools.wraps(fn)
    def scoped_fn(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return scoped_fn


def _sort1(key: jax.Array, idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Single-integer-key stable sort carrying an iota/permutation payload."""
    return lax.sort((key, idx), num_keys=1, is_stable=True)


@_scoped
def gather_many(arrays: list, idx: jax.Array) -> list:
    """Same-index gather of several columns (multi-column join /
    late-materialization shape)."""
    return [a[idx] for a in arrays]


# ---------------------------------------------------------------------------
# factorize: joint dense ranking of key tuples
# ---------------------------------------------------------------------------

@_scoped
def dense_rank(key_data: list[jax.Array], key_valid: list[jax.Array],
               alive: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Assign each alive row a dense group id over its key tuple.

    Returns (gid, num_groups): gid[i] in [0, num_groups) for alive rows and
    == capacity (sentinel segment) for dead rows. Deterministic (sort-based).
    """
    n = alive.shape[0]
    if not key_data:
        # global group: every alive row is group 0 (no sort)
        gid = jnp.where(alive, 0, n).astype(_I32)
        return gid, jnp.any(alive).astype(_I32)
    operands: list[jax.Array] = [(~alive).astype(_I32)]
    for d, v in zip(key_data, key_valid):
        operands.append((~v).astype(_I32))
        operands.append(jnp.where(v & alive, d, jnp.zeros((), d.dtype)))
    num_keys = len(operands)
    out = lax.sort(tuple(operands) + (_iota(n),), num_keys=num_keys,
                   is_stable=True)
    perm = out[-1]
    alive_sorted = out[0] == 0
    diff = jnp.zeros(n, dtype=bool)
    for k in out[1:num_keys]:
        diff = diff | jnp.concatenate([jnp.ones(1, bool), k[1:] != k[:-1]])
    if num_keys == 1:  # no keys: single global group
        diff = jnp.concatenate([jnp.ones(1, bool), jnp.zeros(n - 1, bool)])
    new_group = diff & alive_sorted
    # first alive row must open a group even if `diff` logic missed it
    new_group = new_group | (alive_sorted &
                             jnp.concatenate([jnp.ones(1, bool), ~alive_sorted[:-1]]))
    return _gid_from_sorted(new_group, alive_sorted, perm, n)


@_scoped
def unscatter(perm: jax.Array, values: tuple) -> tuple:
    """Undo a permutation WITHOUT scatter: sort by `perm` (which is a
    permutation of 0..n-1, so sorting restores original row order) carrying
    `values` as payload operands. Measured on TPU: an n-sized scatter costs
    ~60x a 2-operand sort — .at[perm].set() is the single most expensive
    way to invert a permutation on this hardware.
    """
    out = lax.sort((perm,) + tuple(values), num_keys=1, is_stable=True)
    return out[1:]


def _gid_from_sorted(new_group: jax.Array, alive_sorted: jax.Array,
                     perm: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """Shared sorted->gid suffix: cumsum group opens, sort-unscatter back
    through the permutation (dead rows hold the `n` sentinel)."""
    gid_sorted = jnp.cumsum(new_group.astype(_I32)) - 1
    num_groups = jnp.max(jnp.where(alive_sorted, gid_sorted, -1)) + 1
    (gid,) = unscatter(perm, (jnp.where(alive_sorted, gid_sorted, n),))
    return gid, num_groups


# ---------------------------------------------------------------------------
# fast dense_rank tiers: direct-address / packed single-key sort
#
# The multi-operand lax.sort above is O(log^2 n) merge passes over EVERY
# operand (2K+2 arrays for K keys) — the dominant HBM traffic of group-by/
# join programs. When every key is integer-typed (rank_key yields ints for
# str/date/decimal too) and the mixed-radix domain product fits the integer
# dtype, the key tuple packs into ONE integer using runtime min/max ranges
# and a single-key sort (one operand instead of 2K+2) replaces the generic
# path. The packed tier orders groups exactly like the sort-based path
# (value-ascending, nulls last per key), so gids are bit-identical and the
# choice is purely a performance decision, recorded/replayed by the
# executor (_decide_exact_lazy).
# The reference gets this class of kernel from RAPIDS hash-groupby
# (reference nds/power_run_gpu.template); here the TPU-friendly equivalent
# is scatter+cumsum over a bounded domain.
# ---------------------------------------------------------------------------

def _pack_dtype():
    return jnp.int64 if jax.config.read("jax_enable_x64") else _I32


def _key_ranges(key_data: list[jax.Array], key_valid: list[jax.Array],
                alive: jax.Array):
    """Per-key runtime (norm, range, ok): norm in [0, range) with values
    mapped order-preserving to [0, span] and NULL to span+1 (nulls-last,
    matching dense_rank's sort operand order). ok guards span overflow
    (wrapped subtraction on extreme-range keys => key ineligible)."""
    norms, ranges, oks = [], [], []
    for d, v in zip(key_data, key_valid):
        contrib = alive & v
        cnt = jnp.sum(contrib.astype(_I32))
        big = jnp.iinfo(d.dtype).max
        small = jnp.iinfo(d.dtype).min
        m = jnp.min(jnp.where(contrib, d, big))
        mx = jnp.max(jnp.where(contrib, d, small))
        span = jnp.where(cnt > 0, mx - m, jnp.asarray(-1, d.dtype))
        ok = (cnt == 0) | (span >= 0)          # wrapped diff => negative
        span = jnp.maximum(span, -1)
        norm = jnp.where(v, jnp.clip(d - m, 0, span), span + 1)
        norms.append(norm)
        ranges.append((span + 2).astype(_pack_dtype()))
        oks.append(ok)
    return norms, ranges, oks


def _sat_product(ranges: list[jax.Array], cap: int) -> jax.Array:
    """Product of ranges, saturated at cap+1 without overflow: the multiply
    only happens when the result provably fits (the discarded wrapped
    product inside jnp.where is defined-but-unused)."""
    p = jnp.ones((), _pack_dtype())
    for r in ranges:
        rc = jnp.minimum(r, cap + 1)
        p = jnp.where(p > cap // rc, jnp.asarray(cap + 1, p.dtype), p * rc)
    return p


@_scoped
def group_tier(key_data: list[jax.Array], key_valid: list[jax.Array],
               alive: jax.Array) -> jax.Array:
    """Traced packability decision: 1 = the key tuple packs into one
    integer (single-key sort), 0 = the generic multi-operand sort.
    Recorded as an exact schedule decision. (An earlier direct-address
    scatter tier was removed: n-sized scatters measure ~60x a 2-operand
    sort on TPU, so packability is the only distinction that matters.)"""
    _, ranges, oks = _key_ranges(key_data, key_valid, alive)
    ok = jnp.ones((), bool)
    for o in oks:
        ok = ok & o
    pack_cap = (1 << 62) if jax.config.read("jax_enable_x64") else (1 << 30)
    p_pack = _sat_product(ranges, pack_cap)
    return jnp.where(ok & (p_pack <= pack_cap), 1, 0).astype(_I32)


def _pack_keys(key_data: list[jax.Array], key_valid: list[jax.Array],
               alive: jax.Array) -> jax.Array:
    """Mixed-radix packed key per row (caller guarantees the domain fits).

    Recomputes _key_ranges after the group_tier probe: under compiled
    replay the identical reductions CSE into one pass; eager record pays
    the extra pass once per query, on the host CPU."""
    norms, ranges, _ = _key_ranges(key_data, key_valid, alive)
    pd = _pack_dtype()
    c = jnp.zeros(alive.shape[0], pd)
    for norm, r in zip(norms, ranges):
        c = c * r + norm.astype(pd)
    return c


@_scoped
def dense_rank_packsort(key_data: list[jax.Array], key_valid: list[jax.Array],
                        alive: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Tier-2 dense_rank: single packed-key sort (one operand vs 2K+2)."""
    n = alive.shape[0]
    c = _pack_keys(key_data, key_valid, alive)
    key = jnp.where(alive, c, jnp.iinfo(c.dtype).max)
    skey, perm = _sort1(key, _iota(n))
    alive_s = alive[perm]
    new_group = alive_s & jnp.concatenate(
        [jnp.ones(1, bool), skey[1:] != skey[:-1]])
    return _gid_from_sorted(new_group, alive_s, perm, n)


# ---------------------------------------------------------------------------
# filter / compact / limit
# ---------------------------------------------------------------------------

@_scoped
def filter_alive(alive: jax.Array, mask_data: jax.Array,
                 mask_valid: jax.Array) -> jax.Array:
    return alive & mask_data.astype(bool) & mask_valid


@_scoped
def compaction_perm(alive: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Stable permutation bringing alive rows to the front; returns
    (perm, count). Sort-based: a 2-operand lax.sort measures ~60x cheaper
    than the n-sized scatter this used to do (TPU scatters serialize).
    Entries past `count` are dead-row indices (callers mask by count)."""
    n = alive.shape[0]
    _, perm = _sort1((~alive).astype(_I32), _iota(n))
    return perm, jnp.sum(alive.astype(_I32))


@_scoped
def limit_alive(alive: jax.Array, n_keep: int) -> jax.Array:
    """Keep the first `n_keep` alive rows in physical order."""
    pos = jnp.cumsum(alive.astype(_I32)) - 1
    return alive & (pos < n_keep)


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------

@_scoped
def sort_perm(key_data: list[jax.Array], key_valid: list[jax.Array],
              key_specs: tuple, alive: jax.Array) -> jax.Array:
    """Permutation realizing Spark ORDER BY semantics; dead rows go last.

    key_specs: static tuple of (asc, nulls_first) per key (nulls_first may
    be None => Spark default: asc nulls first, desc nulls last).
    """
    n = alive.shape[0]
    operands: list[jax.Array] = [(~alive).astype(_I32)]
    for col, valid, (asc, nulls_first) in zip(key_data, key_valid, key_specs):
        if nulls_first is None:
            nulls_first = asc
        # null rank: 0 => before values, 2 => after values; values rank 1
        null_rank = jnp.where(valid, 1, 0 if nulls_first else 2).astype(_I32)
        operands.append(null_rank)
        d = jnp.where(valid & alive, col, jnp.zeros((), col.dtype))
        if not asc:
            d = (~d) if d.dtype == jnp.bool_ else -d
        operands.append(d)
    out = lax.sort(tuple(operands) + (_iota(n),), num_keys=len(operands),
                   is_stable=True)
    return out[-1]


def sort_specs(keys: list[SortKey]) -> tuple:
    """Static (asc, nulls_first) tuple for sort_perm from bound SortKeys."""
    return tuple((k.asc, k.nulls_first) for k in keys)


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------

# below this segment count, a vectorized (S, n) masked reduce beats the
# scatter-add that segment_sum lowers to by ~600x on TPU (scatters
# serialize; the broadcast+select fuses into the reduction). COMPILED only:
# the eager record pass would materialize the (S, n) intermediate (no
# fusion outside jit), so concrete operands keep the O(n) segment path.
# For INTEGER operands the two forms compute bit-identical values, so
# record/replay schedules agree; float reduction order differs in final
# ULPs between the paths, so float data is kept on the segment path in
# both modes (the dtype gate below) — no schedule decision may ever be
# derived from a path-divergent float reduce.
_MASKED_SEG_MAX = 64


def _seg_multi(pairs: list, gid: jax.Array, num_segments: int) -> list:
    """Several segment reductions over ONE gid vector."""
    return [_seg(d, gid, num_segments, op) for d, op in pairs]


def _seg(data: jax.Array, gid: jax.Array, num_segments: int, op: str) -> jax.Array:
    if (num_segments <= _MASKED_SEG_MAX and isinstance(data, jax.core.Tracer)
            and jnp.issubdtype(data.dtype, jnp.integer)):
        seg_ids = jnp.arange(num_segments, dtype=gid.dtype)
        mask = gid[None, :] == seg_ids[:, None]
        if op == "sum":
            return jnp.where(mask, data[None, :],
                             jnp.zeros((), data.dtype)).sum(axis=1)
        fill = _extreme(data.dtype, op)
        red = jnp.min if op == "min" else jnp.max
        return red(jnp.where(mask, data[None, :], fill), axis=1)
    if op == "sum":
        return jax.ops.segment_sum(data, gid, num_segments=num_segments)
    if op == "min":
        return jax.ops.segment_min(data, gid, num_segments=num_segments)
    if op == "max":
        return jax.ops.segment_max(data, gid, num_segments=num_segments)
    raise AssertionError(op)


@_scoped
def agg_apply(gid: jax.Array, alive: jax.Array, func: str, arg,
              cap_out: int) -> tuple[jax.Array, jax.Array]:
    """One per-group aggregate. `arg` is a (data, valid) tuple or None.

    Returns (values, valid), each length cap_out. gid for dead rows must be
    >= cap_out so their contributions fall outside the segment range.
    """
    int_out = jnp.int64 if jax.config.read("jax_enable_x64") else _I32
    if func == "count_star":
        vals = _seg(jnp.where(alive, 1, 0).astype(_I32), gid, cap_out, "sum")
        return vals.astype(int_out), jnp.ones(cap_out, bool)
    data, valid = arg
    contrib = alive & valid
    # every aggregate needs the per-group contribution count alongside its
    # value reduction
    cnt_op = contrib.astype(int_out)
    if func == "count":
        return _seg(cnt_op, gid, cap_out, "sum"), jnp.ones(cap_out, bool)
    if func == "sum":
        z = jnp.where(contrib, data, jnp.zeros((), data.dtype))
        cnt, s = _seg_multi([(cnt_op, "sum"), (z, "sum")], gid, cap_out)
        return s, cnt > 0
    if func in ("min", "max"):
        big = _extreme(data.dtype, func)
        z = jnp.where(contrib, data, big)
        cnt, vals = _seg_multi([(cnt_op, "sum"), (z, func)], gid, cap_out)
        vals = jnp.where(cnt > 0, vals, jnp.zeros((), data.dtype))
        return vals, cnt > 0
    if func == "avg":
        # integer/decimal inputs under x64: sum EXACTLY in int64 and divide
        # on the tiny per-group output — a per-row f64 cast would run the
        # whole segment reduction in software-emulated f64 on TPU (measured
        # dominant in avg-heavy plans like q9/q22). x32 keeps the float
        # path: i32 sums would wrap past 2^31 on big groups.
        if jnp.issubdtype(data.dtype, jnp.integer) and \
                jax.config.read("jax_enable_x64"):
            z = jnp.where(contrib, data, jnp.zeros((), data.dtype))
        else:
            z = jnp.where(contrib, data, jnp.zeros((), data.dtype)).astype(
                _float_dtype())
        cnt, s = _seg_multi([(cnt_op, "sum"), (z, "sum")], gid, cap_out)
        return (s.astype(_float_dtype()) /
                jnp.maximum(cnt, 1).astype(_float_dtype())), cnt > 0
    if func == "stddev_samp":
        # the squares must accumulate in float (i64 would overflow), but
        # the plain sum stays exact-int for integer inputs (x64 only: i32
        # sums would wrap)
        zf = jnp.where(contrib, data, 0).astype(_float_dtype())
        if jnp.issubdtype(data.dtype, jnp.integer) and \
                jax.config.read("jax_enable_x64"):
            s_op = jnp.where(contrib, data, jnp.zeros((), data.dtype))
        else:
            s_op = zf
        cnt, s, s2 = _seg_multi([(cnt_op, "sum"), (s_op, "sum"),
                                 (zf * zf, "sum")], gid, cap_out)
        s = s.astype(_float_dtype())
        nf = cnt.astype(_float_dtype())
        var = (s2 - s * s / jnp.maximum(nf, 1.0)) / jnp.maximum(nf - 1.0, 1.0)
        return jnp.sqrt(jnp.maximum(var, 0.0)), cnt > 1
    raise NotImplementedError(f"device agg {func}")




def _float_dtype():
    return jnp.float64 if jax.config.read("jax_enable_x64") else jnp.float32


def _extreme(dtype, func: str):
    info_fn = jnp.finfo if jnp.issubdtype(dtype, jnp.floating) else jnp.iinfo
    return jnp.asarray(info_fn(dtype).max if func == "min" else info_fn(dtype).min,
                       dtype=dtype)


@_scoped
def group_representatives(gid: jax.Array, alive: jax.Array,
                          data: jax.Array, valid: jax.Array,
                          cap_out: int) -> tuple[jax.Array, jax.Array]:
    """Per-group key value (all rows in a group share it)."""
    if cap_out <= _MASKED_SEG_MAX and data.dtype != jnp.bool_:
        # masked max-reduce (any row works: the group shares the value);
        # avoids the serialized n-sized scatter
        filled = jnp.where(alive, data, _extreme(data.dtype, "max"))
        vals = _seg(filled, gid, cap_out, "max")
        occupied = _seg(alive.astype(_I32), gid, cap_out, "max") > 0
        pvalid = _seg((alive & valid).astype(_I32), gid, cap_out, "max") > 0
        return jnp.where(occupied, vals, jnp.zeros((), data.dtype)), pvalid
    safe_gid = jnp.where(alive, gid, cap_out)
    padded_vals = jnp.zeros(cap_out + 1, dtype=data.dtype).at[safe_gid].set(data)
    padded_valid = jnp.zeros(cap_out + 1, dtype=bool).at[safe_gid].set(valid)
    return padded_vals[:cap_out], padded_valid[:cap_out]


@_scoped
def distinct_within_group(gid: jax.Array, alive: jax.Array,
                          data: jax.Array, valid: jax.Array
                          ) -> jax.Array:
    """Alive-mask of one representative row per (gid, value) pair (for
    COUNT/SUM DISTINCT): joint rank then first-occurrence selection."""
    n = alive.shape[0]
    pair_gid, _ = dense_rank([gid, jnp.where(valid, data, 0).astype(
        data.dtype), (~valid).astype(_I32)],
        [jnp.ones(n, bool), jnp.ones(n, bool), jnp.ones(n, bool)],
        alive & valid)
    first = jnp.full(n + 1, n, dtype=_I32).at[
        jnp.where(alive & valid, pair_gid, n)].min(_iota(n))
    return (alive & valid) & (first[pair_gid] == _iota(n))


# ---------------------------------------------------------------------------
# sorted aggregation: scans over key-sorted rows instead of segment scatters
# ---------------------------------------------------------------------------

@_scoped
def sorted_agg_scan(vals: jax.Array, new_group: jax.Array, op) -> jax.Array:
    """Inclusive within-group scan over KEY-SORTED rows (group totals sit at
    group-end rows). This is the scatter-free replacement for
    segment_sum/min/max: TPU segment_* lowers to serialized scatter-adds
    (~100ns/row measured); a log-depth associative scan is ~25x cheaper."""
    return _seg_scan(vals, new_group, op)


@_scoped
def group_ends(new_group: jax.Array, alive_sorted: jax.Array) -> jax.Array:
    """Row mask of each group's LAST alive row in sorted order."""
    n = new_group.shape[0]
    next_new = jnp.concatenate([new_group[1:], jnp.ones(1, bool)])
    next_dead = jnp.concatenate([~alive_sorted[1:], jnp.ones(1, bool)])
    return alive_sorted & (next_new | next_dead)


# ---------------------------------------------------------------------------
# window functions
# ---------------------------------------------------------------------------

def _seg_scan(vals: jax.Array, new_part: jax.Array, op) -> jax.Array:
    """Inclusive within-segment scan of `op` (reset at new_part) — the
    classic reset-semiring associative_scan, TPU-friendly (log-depth)."""
    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, op(va, vb))
    _, out = lax.associative_scan(comb, (new_part, vals))
    return out


@_scoped
def window_ordered_core(sgid: jax.Array, tie_data: list[jax.Array],
                        tie_valid: list[jax.Array], arg, func: str
                        ) -> tuple[jax.Array, jax.Array]:
    """Ordered-window values over rows ALREADY sorted by (partition, order).

    sgid: sorted partition ids (dead rows hold a trailing sentinel id).
    tie_data/tie_valid: sorted order-key columns for RANGE tie detection.
    arg: (data, valid) in sorted order, or None (rank family / count_star).
    Returns (values, valid) in sorted order; caller scatters back via the
    sort permutation and masks by `alive`. RANGE frame semantics: every row
    of a tie run takes the run's last cumulative value (Spark default
    RANGE BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW).
    """
    n = sgid.shape[0]
    iota = _iota(n)
    true1 = jnp.ones(1, bool)
    new_part = jnp.concatenate([true1, sgid[1:] != sgid[:-1]])
    same = jnp.ones(n, bool)
    for d, v in zip(tie_data, tie_valid):
        eq = jnp.concatenate([jnp.zeros(1, bool),
                              (d[1:] == d[:-1]) & (v[1:] == v[:-1])])
        same = same & eq
    same = same & ~new_part
    # index of the row's partition start / tie-run start (starts are
    # monotically increasing, so a global cummax over flagged indices works)
    part_start = lax.cummax(jnp.where(new_part, iota, 0))
    pos_in_part = iota - part_start

    if func == "row_number":
        return pos_in_part + 1, jnp.ones(n, bool)
    if func == "rank":
        run_start = lax.cummax(jnp.where(~same, iota, 0))
        return run_start - part_start + 1, jnp.ones(n, bool)
    if func == "dense_rank":
        bump = (~same) & ~new_part
        cb = jnp.cumsum(bump.astype(_I32))
        return cb - cb[part_start] + 1, jnp.ones(n, bool)

    # cumulative aggregates (RANGE: ties share the run-final value)
    new_run = ~same  # run == maximal tie group; every new_part starts a run
    run_id = jnp.cumsum(new_run.astype(_I32)) - 1
    last_of_run = jax.ops.segment_max(iota, run_id, num_segments=n)

    def ties_last(x):
        return x[last_of_run[run_id]]

    if func == "count_star":
        return ties_last(pos_in_part + 1), jnp.ones(n, bool)
    data, valid = arg
    fd = _float_dtype()
    run_count = _seg_scan(valid.astype(_I32), new_part, jnp.add)
    run_count = ties_last(run_count)
    out_valid = run_count > 0
    if func == "count":
        return run_count, jnp.ones(n, bool)
    if func in ("sum", "avg"):
        # integer inputs accumulate in the integer dtype (exact, and avoids
        # per-row software-f64 scans on TPU; f32 would lose exactness past
        # 2^24) — avg divides only the final cumulative values. avg keeps
        # the float path in x32 (i32 cumsums would wrap on big partitions);
        # sum keeps historical int accumulation in both modes.
        int_in = jnp.issubdtype(data.dtype, jnp.integer)
        acc = data.dtype if (int_in and (
            func == "sum" or jax.config.read("jax_enable_x64"))) else fd
        w = jnp.where(valid, data.astype(acc), jnp.zeros((), acc))
        run_sum = ties_last(_seg_scan(w, new_part, jnp.add))
        if func == "sum":
            return run_sum, out_valid
        return (run_sum.astype(fd) /
                jnp.maximum(run_count, 1).astype(fd)), out_valid
    if func in ("min", "max"):
        # accumulate in the NATIVE dtype: int keys past 2^24 would round
        # in f32 (TPU x32), and f32 round-trips would corrupt exact mins
        ext = _extreme(data.dtype, func)
        vals = jnp.where(valid, data, ext)
        op = jnp.minimum if func == "min" else jnp.maximum
        out = ties_last(_seg_scan(vals, new_part, op))
        out = jnp.where(out_valid, out, jnp.zeros((), data.dtype))
        return out, out_valid
    raise NotImplementedError(f"device window {func}")


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------

@_scoped
def build_side(gid_right: jax.Array, alive_right: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """Sort right-side gids (dead rows pushed to +inf); returns (sorted_gid, perm)."""
    key = jnp.where(alive_right, gid_right, jnp.iinfo(_I32).max)
    return _sort1(key, _iota(alive_right.shape[0]))


@_scoped
def probe_counts_by_gid(build_gid: jax.Array, build_alive: jax.Array,
                        probe_gid: jax.Array, probe_alive: jax.Array,
                        gid_cap: int) -> tuple[jax.Array, jax.Array]:
    """Per-probe-row match range in the gid-sorted build side: (start, count).

    Sort-free probe (searchsorted's vmapped while-loop is pathologically slow
    on TPU inside large programs): per-gid build counts via segment_sum, run
    offsets via exclusive cumsum — the gid-sorted build side (build_side)
    lays runs out in exactly that order — then a gather per probe row.
    gid_cap: static bound on distinct gids (callers pass lcap+rcap).
    """
    counts = jax.ops.segment_sum(
        build_alive.astype(_I32),
        jnp.where(build_alive, build_gid, gid_cap), num_segments=gid_cap)
    offsets = jnp.cumsum(counts) - counts      # exclusive prefix per gid
    safe = jnp.clip(probe_gid, 0, gid_cap - 1)
    in_range = probe_alive & (probe_gid >= 0) & (probe_gid < gid_cap)
    lo = jnp.where(in_range, offsets[safe], 0)
    cnt = jnp.where(in_range, counts[safe], 0)
    return lo.astype(_I32), cnt.astype(_I32)


@_scoped
def expand_join(lo: jax.Array, cnt: jax.Array, probe_alive: jax.Array,
                cap_out: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Materialize (left_row, build_sorted_pos) pairs for an inner join.

    cap_out must be >= total matches (caller host-syncs the total).
    Returns (left_idx, build_pos, alive_out) each of length cap_out.
    Run expansion is scatter-markers + cummax (no searchsorted): each probe
    row with matches drops its row id at its output-run start; cummax
    propagates the id across the run.
    """
    n = cnt.shape[0]
    cum = jnp.cumsum(cnt)
    total = cum[-1]
    starts = cum - cnt
    rows = _iota(n)
    has = probe_alive & (cnt > 0)
    marker = jnp.zeros(cap_out + 1, _I32).at[
        jnp.where(has, jnp.minimum(starts, cap_out), cap_out)].max(rows)
    left_pos = lax.cummax(marker[:cap_out])
    left_safe = jnp.minimum(left_pos, n - 1)
    j = _iota(cap_out)
    k = j - starts[left_safe]
    build_pos = lo[left_safe] + k
    alive_out = j < total
    return left_safe, build_pos, alive_out
