"""The public lowerings of ``jax_backend/kernels.py`` held to references.

Each case holds one lowering to a numpy (or plain Python) reference written
here AND its eager output to its ``jax.jit`` output. The record pass runs the
kernels eagerly on concrete arrays, the compiled replay traces them: a
schedule recorded from one is checked against the other, so wherever the
inputs are integers — every schedule-deciding path — the two must agree bit
for bit (the rule above ``kernels._MASKED_SEG_MAX``). Float inputs agree to
1e-12 relative. A few thousand rows a case, one file, one worker: the net
under a rewrite of these kernels.

The last three cases run whole statements: record pass against compiled
replay against the ``ops.py`` numpy oracle.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session, arrow_bridge
from nds_tpu.engine.jax_backend import kernels

I32_MAX = np.iinfo(np.int32).max


def both(fn, *args):
    """``fn(*args)`` run eagerly and under ``jax.jit``, as numpy trees."""
    eager = jax.tree.map(np.asarray, fn(*args))
    jitted = jax.tree.map(np.asarray, jax.jit(fn)(*args))
    return eager, jitted


def same(got, want, exact: bool = True) -> None:
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    if exact or got.dtype.kind in "biu":
        assert np.array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)


def same_trees(got, want, exact: bool = True) -> None:
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        same(g, w, exact)


def column(rng, n, dtype, null_frac=0.1):
    """(data, valid) with canonical zeros in the null slots."""
    if np.dtype(dtype).kind == "f":
        data = rng.uniform(1, 100, n).astype(dtype)     # no cancellation
    else:
        data = rng.integers(-50, 50, n).astype(dtype)
    valid = rng.random(n) >= null_frac
    return np.where(valid, data, np.zeros((), dtype)), valid


# ---------------------------------------------------------------------------
# agg_apply
# ---------------------------------------------------------------------------

AGG_FUNCS = ["count_star", "count", "sum", "min", "max", "avg", "stddev_samp"]


def ref_agg(func, gid, alive, data, valid, cap):
    contrib = alive if func == "count_star" else alive & valid
    vals, ok = [], []
    for g in range(cap):
        rows = contrib & (gid == g)
        cnt = int(rows.sum())
        x = data[rows] if data is not None else None
        if func in ("count_star", "count"):
            vals.append(cnt), ok.append(True)
        elif func == "sum":
            vals.append(x.sum() if cnt else 0), ok.append(cnt > 0)
        elif func in ("min", "max"):
            vals.append(getattr(x, func)() if cnt else 0), ok.append(cnt > 0)
        elif func == "avg":
            vals.append(np.float64(x.sum()) / max(cnt, 1)), ok.append(cnt > 0)
        else:
            s, s2 = np.float64(x.sum()), (x.astype(np.float64) ** 2).sum()
            var = (s2 - s * s / max(cnt, 1.0)) / max(cnt - 1.0, 1.0)
            vals.append(np.sqrt(max(var, 0.0))), ok.append(cnt > 1)
    return np.asarray(vals), np.asarray(ok)


@pytest.mark.parametrize("dtype", [np.int64, np.float64],
                         ids=["int64", "float64"])
@pytest.mark.parametrize("func", AGG_FUNCS)
def test_agg_apply(func, dtype):
    rng = np.random.default_rng(AGG_FUNCS.index(func))
    n, cap = 3000, 37               # cap under _MASKED_SEG_MAX: jit masks
    data, valid = column(rng, n, dtype)
    alive = rng.random(n) < 0.7
    groups = rng.integers(0, cap - 1, n)          # group cap-1 stays empty
    gid = np.where(alive, groups, cap).astype(np.int32)
    arg = None if func == "count_star" else (jnp.asarray(data),
                                             jnp.asarray(valid))
    eager, jitted = both(
        lambda g, a, x: kernels.agg_apply(g, a, func, x, cap),
        jnp.asarray(gid), jnp.asarray(alive), arg)
    want = ref_agg(func, gid, alive, None if arg is None else data, valid,
                   cap)
    exact = dtype is np.int64
    same_trees(eager, want, exact)
    same_trees(jitted, eager, exact)
    assert not eager[1][cap - 1] or func in ("count_star", "count")


# ---------------------------------------------------------------------------
# _seg: the masked / segment switch at _MASKED_SEG_MAX
# ---------------------------------------------------------------------------

def ref_seg(data, gid, num, op):
    info = np.iinfo(data.dtype)
    live = gid < num
    if op == "sum":
        out = np.zeros(num, data.dtype)
        np.add.at(out, gid[live], data[live])
    elif op == "min":
        out = np.full(num, info.max, data.dtype)
        np.minimum.at(out, gid[live], data[live])
    else:
        out = np.full(num, info.min, data.dtype)
        np.maximum.at(out, gid[live], data[live])
    return out


@pytest.mark.parametrize("num", [1, 63, 64, 65, 1000])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_seg(op, num):
    assert kernels._MASKED_SEG_MAX == 64
    rng = np.random.default_rng(num)
    n = 4096
    gid = rng.integers(0, num + 1, n).astype(np.int32)  # num: dead rows
    gid[gid == num // 2] = num      # an empty segment reads the identity
    data = rng.integers(-1000, 1000, n).astype(np.int64)
    eager, jitted = both(lambda d, g: kernels._seg(d, g, num, op),
                         jnp.asarray(data), jnp.asarray(gid))
    same(eager, ref_seg(data, gid, num, op))
    same(jitted, eager)


# ---------------------------------------------------------------------------
# dense_rank_packsort against dense_rank
# ---------------------------------------------------------------------------

def ref_dense_rank(cols, alive):
    """Groups in key order, valid values ascending, NULL last per key."""
    n = len(alive)
    keys = [tuple((not v[i], int(d[i]) if v[i] else 0) for d, v in cols)
            for i in range(n)]
    order = {k: g for g, k in enumerate(sorted({k for k, a in
                                                zip(keys, alive) if a}))}
    gid = np.asarray([order[k] if a else n for k, a in zip(keys, alive)],
                     np.int32)
    return gid, len(order)


@pytest.mark.parametrize("case", ["random", "all_null", "all_dead",
                                  "single_group", "cap_edge"])
def test_dense_rank_packsort_is_dense_rank(case):
    rng = np.random.default_rng(17)
    n = 4096 if case == "cap_edge" else 3000
    data, valid = column(rng, n, np.int64)
    data2 = rng.integers(0, 4, n).astype(np.int32)
    valid2 = rng.random(n) >= 0.05
    alive = rng.random(n) < 0.8
    if case == "all_null":
        data, valid = np.zeros(n, np.int64), np.zeros(n, bool)
    elif case == "all_dead":
        alive = np.zeros(n, bool)
    elif case == "single_group":
        data, valid = np.zeros(n, np.int64), np.ones(n, bool)
        data2, valid2 = np.zeros(n, np.int32), np.ones(n, bool)
    elif case == "cap_edge":
        alive = np.ones(n, bool)
    data2 = np.where(valid2, data2, 0).astype(np.int32)
    args = ([jnp.asarray(data), jnp.asarray(data2)],
            [jnp.asarray(valid), jnp.asarray(valid2)], jnp.asarray(alive))
    want = ref_dense_rank([(data, valid), (data2, valid2)], alive)
    for fn in (kernels.dense_rank_packsort, kernels.dense_rank):
        eager, jitted = both(fn, *args)
        same_trees(eager, want)
        same_trees(jitted, eager)
        assert eager[0].dtype == np.int32


# ---------------------------------------------------------------------------
# compaction, unscatter, build side, filter, limit
# ---------------------------------------------------------------------------

def test_compaction_perm():
    rng = np.random.default_rng(23)
    alive = rng.random(3000) < 0.6
    eager, jitted = both(kernels.compaction_perm, jnp.asarray(alive))
    same_trees(eager, (np.argsort(~alive, kind="stable"), alive.sum()))
    same_trees(jitted, eager)


def test_unscatter():
    rng = np.random.default_rng(24)
    n = 3000
    perm = rng.permutation(n).astype(np.int32)
    values = (rng.integers(-9, 9, n), rng.random(n) < 0.5, rng.random(n))
    want = []
    for v in values:
        out = np.empty_like(v)
        out[perm] = v
        want.append(out)
    eager, jitted = both(kernels.unscatter, jnp.asarray(perm),
                         tuple(jnp.asarray(v) for v in values))
    same_trees(eager, want)         # a permutation moves bits: floats too
    same_trees(jitted, eager)


def test_build_side():
    rng = np.random.default_rng(25)
    n = 3000
    gid = rng.integers(0, 64, n).astype(np.int32)
    alive = rng.random(n) < 0.6
    key = np.where(alive, gid, I32_MAX)
    perm = np.argsort(key, kind="stable")
    eager, jitted = both(kernels.build_side, jnp.asarray(gid),
                         jnp.asarray(alive))
    same_trees(eager, (key[perm], perm))
    same_trees(jitted, eager)


def test_filter_alive():
    rng = np.random.default_rng(26)
    n = 3000
    alive, valid = rng.random(n) < 0.7, rng.random(n) < 0.9
    mask = rng.integers(0, 2, n).astype(np.int32)
    eager, jitted = both(kernels.filter_alive, jnp.asarray(alive),
                         jnp.asarray(mask), jnp.asarray(valid))
    same(eager, alive & (mask != 0) & valid)
    same(jitted, eager)


def test_limit_alive():
    rng = np.random.default_rng(27)
    alive = rng.random(3000) < 0.5
    keep = 100
    want = alive.copy()
    want[np.flatnonzero(alive)[keep:]] = False
    eager, jitted = both(lambda a: kernels.limit_alive(a, keep),
                         jnp.asarray(alive))
    same(eager, want)
    same(jitted, eager)
    assert eager.sum() == keep


@pytest.mark.parametrize("cap", [8, 100], ids=["masked", "scatter"])
def test_group_representatives(cap):
    """Both sides of the ``cap_out <= _MASKED_SEG_MAX`` switch."""
    rng = np.random.default_rng(cap)
    n = 3000
    group_val = rng.integers(-99, 99, cap).astype(np.int64)
    group_ok = rng.random(cap) < 0.8
    groups = rng.integers(0, cap - 1, n)          # group cap-1 stays empty
    alive = rng.random(n) < 0.7
    gid = np.where(alive, groups, cap).astype(np.int32)
    valid = group_ok[groups]
    data = np.where(valid, group_val[groups], 0)
    occupied = np.bincount(groups[alive], minlength=cap) > 0
    want = (np.where(occupied & group_ok, group_val, 0), occupied & group_ok)
    eager, jitted = both(
        lambda g, a, d, v: kernels.group_representatives(g, a, d, v, cap),
        jnp.asarray(gid), jnp.asarray(alive), jnp.asarray(data),
        jnp.asarray(valid))
    same_trees(eager, want)
    same_trees(jitted, eager)


# ---------------------------------------------------------------------------
# probe_counts_by_gid + expand_join against a nested-loop join
# ---------------------------------------------------------------------------

def kernel_join(lkey, lvalid, lalive, rkey, rvalid, ralive, cap_out):
    """The generic inner join as ``JaxExecutor`` assembles it: one joint
    dense rank over both sides' keys, the build side sorted by gid, a
    match range per probe row, the ranges expanded to pairs."""
    lcap = lkey.shape[0]
    match_alive = jnp.concatenate([lalive & lvalid, ralive & rvalid])
    gid, _ = kernels.dense_rank(
        [jnp.concatenate([lkey, rkey])],
        [jnp.ones(match_alive.shape[0], bool)], match_alive)
    l_gid, r_gid = gid[:lcap], gid[lcap:]
    _, perm_r = kernels.build_side(
        jnp.where(match_alive[lcap:], r_gid, I32_MAX), ralive & rvalid)
    lo, cnt = kernels.probe_counts_by_gid(
        r_gid, ralive & rvalid, l_gid, lalive & lvalid,
        gid_cap=match_alive.shape[0])
    left_idx, build_pos, alive_out = kernels.expand_join(lo, cnt, lalive,
                                                         cap_out)
    right_rows = perm_r[jnp.clip(build_pos, 0, rkey.shape[0] - 1)]
    return left_idx, right_rows, alive_out, cnt


def nested_loop_join(lkey, lvalid, lalive, rkey, rvalid, ralive):
    pairs = []
    for i in range(len(lkey)):
        if not (lalive[i] and lvalid[i]):
            continue
        for r in range(len(rkey)):
            if ralive[r] and rvalid[r] and rkey[r] == lkey[i]:
                pairs.append((i, r))
    return pairs


@pytest.mark.parametrize("case", ["unique_build", "duplicate_build",
                                  "no_match", "all_dead_probe", "null_keys"])
def test_join_kernels_are_a_nested_loop_join(case):
    rng = np.random.default_rng(31)
    nl, nr = 1500, 120
    lkey = rng.integers(0, nr + 20, nl).astype(np.int64)
    rkey = rng.permutation(nr).astype(np.int64)
    lvalid, rvalid = np.ones(nl, bool), np.ones(nr, bool)
    lalive, ralive = rng.random(nl) < 0.85, rng.random(nr) < 0.85
    if case == "duplicate_build":
        rkey = rng.integers(0, 30, nr).astype(np.int64)
    elif case == "no_match":
        lkey = lkey + 1000
    elif case == "all_dead_probe":
        lalive = np.zeros(nl, bool)
    elif case == "null_keys":
        lvalid, rvalid = rng.random(nl) < 0.8, rng.random(nr) < 0.8
        lkey, rkey = np.where(lvalid, lkey, 0), np.where(rvalid, rkey, 0)
    want = nested_loop_join(lkey, lvalid, lalive, rkey, rvalid, ralive)
    assert bool(want) == (case not in ("no_match", "all_dead_probe"))
    cap_out = 1 << max(len(want), 1).bit_length()
    eager, jitted = both(
        partial(kernel_join, cap_out=cap_out),
        *(jnp.asarray(a) for a in (lkey, lvalid, lalive, rkey, rvalid,
                                   ralive)))
    left_idx, right_rows, alive_out, cnt = eager
    assert int(alive_out.sum()) == int(cnt.sum()) == len(want)
    assert list(zip(left_idx[alive_out].tolist(),
                    right_rows[alive_out].tolist())) == want
    same_trees(jitted, eager)


# ---------------------------------------------------------------------------
# gather_many, sort_perm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float64, np.bool_],
                         ids=["int32", "int64", "float64", "bool"])
def test_gather_many(dtype):
    rng = np.random.default_rng(41)
    n = 3000
    if dtype is np.bool_:
        src = rng.random(n) < 0.5
    elif dtype is np.float64:
        src = rng.random(n)
    else:
        src = rng.integers(-1 << 30, 1 << 30, n).astype(dtype)
    other = rng.integers(0, 100, n).astype(np.int32)
    idx = rng.integers(0, n, 5000).astype(np.int32)
    eager, jitted = both(kernels.gather_many,
                         [jnp.asarray(src), jnp.asarray(other)],
                         jnp.asarray(idx))
    same_trees(eager, [src[idx], other[idx]])       # reads move bits
    same_trees(jitted, eager)
    assert eager[0].dtype == np.dtype(dtype)


@pytest.mark.parametrize("nulls_first", [True, False],
                         ids=["nulls_first", "nulls_last"])
@pytest.mark.parametrize("asc", [True, False], ids=["asc", "desc"])
def test_sort_perm(asc, nulls_first):
    """ORDER BY k1 <asc|desc> NULLS <FIRST|LAST>, k2 DESC (Spark's default
    for DESC: nulls last); ties keep row order, dead rows go last."""
    rng = np.random.default_rng(43)
    n = 3000
    k1, v1 = column(rng, n, np.int64, null_frac=0.15)    # heavy ties
    k2, v2 = column(rng, n, np.int32, null_frac=0.15)
    alive = rng.random(n) < 0.8

    def rank(i):
        first = (0 if nulls_first else 2, 0) if not v1[i] else \
            (1, int(k1[i]) if asc else -int(k1[i]))
        second = (2, 0) if not v2[i] else (1, -int(k2[i]))
        return (first, second, i)

    want = sorted(np.flatnonzero(alive).tolist(), key=rank)
    specs = ((asc, nulls_first), (False, None))
    eager, jitted = both(
        lambda d, v, a: kernels.sort_perm(d, v, specs, a),
        [jnp.asarray(k1), jnp.asarray(k2)],
        [jnp.asarray(v1), jnp.asarray(v2)], jnp.asarray(alive))
    same(eager[:len(want)], np.asarray(want, np.int32))
    assert not alive[eager[len(want):]].any() and \
        len(set(eager.tolist())) == n
    same(jitted, eager)


# ---------------------------------------------------------------------------
# whole statements: record run, compiled replay, the ops.py oracle
# ---------------------------------------------------------------------------

Q_AGG = ("SELECT d.grp, COUNT(*) c, SUM(f.qty) s, MIN(f.day) mn, "
         "MAX(f.price) mx, AVG(f.qty) a FROM fact f JOIN dim d "
         "ON f.fk = d.dk WHERE f.day < 300 GROUP BY d.grp ORDER BY d.grp")
Q_WINDOW = ("SELECT dk, grp, RANK() OVER (PARTITION BY grp ORDER BY dk) r "
            "FROM dim ORDER BY grp, dk")
Q_TOPK = ("SELECT fk, qty FROM fact WHERE qty IS NOT NULL "
          "ORDER BY qty DESC, fk LIMIT 50")


@pytest.fixture(scope="module")
def tables():
    rng = np.random.default_rng(7)
    n_fact, n_dim = 9_100, 300
    qty = rng.integers(1, 50, n_fact).astype(object)
    qty[rng.random(n_fact) < 0.07] = None
    fact = pa.table({
        "fk": pa.array(rng.integers(0, n_dim + 9, n_fact), type=pa.int32()),
        "qty": pa.array(list(qty), type=pa.int32()),
        "price": pa.array(np.round(rng.uniform(1, 100, n_fact), 2)),
        "day": pa.array(rng.integers(0, 365, n_fact), type=pa.int32())})
    dim = pa.table({"dk": pa.array(np.arange(n_dim), type=pa.int32()),
                    "grp": pa.array((np.arange(n_dim) % 13)
                                    .astype(np.int32))})
    return fact, dim


@pytest.mark.parametrize("q", [Q_AGG, Q_WINDOW, Q_TOPK],
                         ids=["agg", "window", "topk"])
def test_statement_record_replay_and_oracle(tables, q):
    fact, dim = tables
    s = Session(EngineConfig())
    s.register_arrow("fact", fact)
    s.register_arrow("dim", dim)

    def rows(backend):
        return arrow_bridge.to_arrow(s.sql(q, backend=backend)).to_pylist()

    recorded = rows("jax")
    assert s.last_exec_stats["mode"] == "record"
    replayed = rows("jax")
    assert s.last_exec_stats["mode"] in ("compile+run", "compiled")
    assert recorded == replayed == rows("numpy") and recorded
