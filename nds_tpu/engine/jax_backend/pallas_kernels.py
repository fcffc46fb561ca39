"""TPU Pallas implementations of the relational hot loops.

PR 6's per-program device-time attribution names three kernel families as
the whole slice's device time (q10+q7 = 85% at <0.5% roofline each, PERF.md
round 10): the segmented sorts behind dense_rank/group-by, the
factorize->scatter-add aggregation pipeline, and the join/late-mat
random-access gathers (q72: ~10-25 ns/element through XLA's generic
lowering).  Each family gets a hand-tiled Pallas kernel here, swapped in
behind a per-op flag (``EngineConfig.pallas_ops``, a subset of
{"sort", "groupby", "gather"}) with the existing XLA lowering as the
bit-identical fallback:

- ``sort_pairs``          VMEM-blocked bitonic/merge sort over (key, idx)
                          pairs.  Blocks sort locally in VMEM (the first
                          log2(B) stages of the global bitonic network are
                          intra-block), cross-block compare-exchange passes
                          (distance >= B) run as streaming elementwise XLA
                          (already bandwidth-optimal), and each stage's
                          trailing intra-block merge network runs as one
                          Pallas pass over VMEM-resident blocks.  The
                          comparator is the total order (key, idx), so the
                          result is BIT-IDENTICAL to the stable
                          ``lax.sort`` it replaces.
- ``seg_reduce[_multi]``  fused group-by partial aggregation: per tile of
                          rows, one (segments x tile) membership mask is
                          materialized in VMEM and every requested
                          SUM/COUNT/MIN/MAX operand reduces through it into
                          segment partials accumulated across the
                          (sequential) grid — replacing the serialized
                          scatter-adds ``jax.ops.segment_*`` lowers to.
                          Integer sums and min/max are order-independent,
                          so results are bit-identical; float sums stay on
                          the XLA path (reduction-order ULPs).
- ``take[_many]``         batched multi-column gather: the source columns
                          stage whole in VMEM and index tiles stream
                          through them — the q72 late-materialization
                          fusion class (scripts/kernel_bench.py, the
                          promoted exp_gather experiment, measures the
                          VMEM-staged form against the HBM gather).  Gather
                          is a pure permutation read: bit-identical by
                          construction.

Dispatch is a thread-local op set installed by the executor
(``set_active``); compiled replay traces under the same set because
``CompiledQuery`` carries it, and program caches key on it (the executor's
shared-program fingerprint and the session's stream-config key).

Platform handling (``probe``): on a TPU backend kernels compile through
Mosaic and never run interpreted (the host record pass of a TPU process
takes the XLA lowering instead, JaxExecutor.execute); on the CPU backend
they run in Pallas interpret mode — tier-1 CI exercises the real kernel
bodies under ``JAX_PLATFORMS=cpu``. A REQUESTED op that cannot run is an
error, never a quiet XLA substitute: on a backend without a TPU Pallas
lowering ``op_active`` raises ``PallasLoweringError``, and on a TPU every
kernel signature is compiled standalone once (``_checked``) so a Mosaic
refusal names the kernel and carries the compiler's message.
"""
from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ...obs import metrics as _metrics
from ...obs.log import get_logger

_I32 = jnp.int32

#: the ops a config may enable
VALID_OPS = frozenset({"sort", "groupby", "gather"})

# -- tiling parameters (static; see ISSUE 7 / pallas_guide VMEM sizing) ------
#: rows per VMEM sort block (power of two; i64 key + i32 idx at 1<<10 rows
#: keeps the block working set ~12 KB, far under the ~16 MB/core VMEM)
SORT_BLOCK = 1 << 10
#: seg_reduce eligibility cap: the per-tile membership mask is
#: (segments x tile) in VMEM, bounded by GROUPBY_MASK_ELEMS — the tile
#: adapts so small segment counts take big tiles (few grid steps) and the
#: 2048-segment worst case stays at a 256-row tile (4 MB i64 broadcast)
GROUPBY_MAX_SEGMENTS = 1 << 11
GROUPBY_MASK_ELEMS = 1 << 19
GROUPBY_MAX_TILE = 1 << 12
#: index rows per gather tile
GATHER_BLOCK = 1 << 12
#: VMEM budget for the staged gather sources of ONE kernel call; larger
#: column batches split across calls, single columns past it fall back
GATHER_SRC_BYTES = 4 << 20
# Minimum row counts for a call site to ride the Pallas path at all.
# Small arrays keep the XLA lowering: kernel-launch overhead dominates
# them on TPU, and every pallas call SITE costs one compile — a q10-class
# plan has dozens of dimension-scale sorts/gathers whose kernels would
# never earn their compile back. Shapes are static per compiled program,
# so the gate is deterministic; both sides are bit-identical, so a
# record/replay shape difference (streaming inflation) is benign.
SORT_MIN_ROWS = 1 << 13
GATHER_MIN_ROWS = 1 << 12
GROUPBY_MIN_ROWS = 1 << 12


# ---------------------------------------------------------------------------
# platform probe + per-executor op activation
# ---------------------------------------------------------------------------

def scoped(fn):
    """Run a kernel entry point under ``jax.named_scope(<its name>)``:
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


class PallasLoweringError(Exception):
    """A requested Pallas kernel cannot run on this backend: no TPU Pallas
    lowering here, or Mosaic refused the kernel. Names the kernel and
    carries the compiler's message. Deliberately none of the executor's
    _NOJIT_ERRORS (nor a NotImplementedError): a requested kernel is never
    quietly replaced by the XLA lowering or a host fallback."""


_PROBE: Optional[tuple] = None


def probe() -> tuple[str, str]:
    """-> (mode, reason): mode is "tpu" (compiled Mosaic), "interpret"
    (CPU backend, Pallas interpreter — the tier-1 CI configuration), or
    "off" (unusable; reason says why). Cached for the process."""
    global _PROBE
    if _PROBE is not None:
        return _PROBE
    try:
        from jax.experimental import pallas as _pl            # noqa: F401
        from jax.experimental.pallas import tpu as _pltpu     # noqa: F401
    except Exception as e:          # pragma: no cover - env-dependent
        _PROBE = ("off", f"pallas import failed: {type(e).__name__}: {e}")
        return _PROBE
    backend = jax.default_backend()
    if backend == "tpu":
        _PROBE = ("tpu", "")
    elif backend == "cpu":
        _PROBE = ("interpret", "cpu backend: pallas interpret mode")
    else:
        _PROBE = ("off", f"no TPU pallas lowering on backend {backend!r}")
    return _PROBE


def _reset_probe_for_tests() -> None:
    global _PROBE
    _PROBE = None


def parse_ops(spec) -> frozenset:
    """Validated op set from a config tuple / comma string; unknown names
    are dropped with one warning (graceful degradation, never a crash)."""
    if spec is None:
        return frozenset()
    if isinstance(spec, str):
        spec = [s for s in spec.split(",")]
    ops = {s.strip() for s in spec if s and s.strip()}
    bad = ops - VALID_OPS
    if bad:
        get_logger("pallas").warning(
            "ignoring unknown pallas_ops %s (valid: %s)",
            sorted(bad), sorted(VALID_OPS))
    return frozenset(ops & VALID_OPS)


_tls = threading.local()


def set_active(ops: frozenset) -> None:
    """Install the executing plan's op set (thread-local: concurrent
    compile-pool traces each carry their executor's set)."""
    _tls.ops = ops


def active_ops() -> frozenset:
    return getattr(_tls, "ops", frozenset())


def op_active(op: str) -> bool:
    """Is `op` enabled for the in-flight execution? A requested op on a
    platform that cannot run it raises PallasLoweringError naming it."""
    if op not in active_ops():
        return False
    mode, reason = probe()
    if mode == "off":
        raise PallasLoweringError(
            f"pallas op {op!r} was requested (pallas_ops) but cannot run "
            f"here: {reason}")
    return True


def _interpret() -> bool:
    return probe()[0] != "tpu"


def _pl():
    from jax.experimental import pallas as pl
    return pl


def _checked(name: str, call, arg_specs: list):
    """On a TPU backend, compile `call` standalone once for its signature
    (the kernel factories are lru_cached) so a Mosaic refusal surfaces as
    PallasLoweringError naming the kernel with the compiler's message —
    inside a whole-plan program it would be one anonymous compile failure.
    Interpret mode has nothing to lower."""
    if _interpret():
        return call
    try:
        jax.jit(call).lower(*arg_specs).compile()
    except Exception as e:
        raise PallasLoweringError(
            f"pallas kernel {name} does not lower on "
            f"{jax.devices()[0].device_kind}: {type(e).__name__}: {e}") from e
    return call


def _bspec(shape, index_map):
    """BlockSpec pinned to VMEM on real TPUs (interpret mode ignores
    memory spaces; passing them keeps one code path)."""
    pl = _pl()
    if _interpret():
        return pl.BlockSpec(shape, index_map)
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


# ---------------------------------------------------------------------------
# (a) tiled segmented sort: VMEM-blocked bitonic/merge network
# ---------------------------------------------------------------------------

def _cmpex(kk: jax.Array, ii: jax.Array, d: int, s: int, start):
    """One bitonic compare-exchange pass at distance `d` of global stage
    `s` over flat (key, idx) arrays whose first element has global index
    `start` (python int for whole-array passes, traced for in-kernel
    blocks). Comparator: lexicographic (key, idx) — a total order, so the
    full network reproduces the stable sort exactly."""
    B = kk.shape[0]
    k3 = kk.reshape(-1, 2, d)
    i3 = ii.reshape(-1, 2, d)
    nb = k3.shape[0]
    gi = lax.broadcasted_iota(_I32, (nb, 1, 1), 0)
    # each (2d)-pair-group sits inside one direction block of size 2^(s+1)
    asc = (((start + gi * 2 * d) >> (s + 1)) & 1) == 0
    ka, kb = k3[:, 0:1], k3[:, 1:2]
    ia, ib = i3[:, 0:1], i3[:, 1:2]
    a_gt_b = (ka > kb) | ((ka == kb) & (ia > ib))
    b_gt_a = (kb > ka) | ((kb == ka) & (ib > ia))
    swap = jnp.where(asc, a_gt_b, b_gt_a)
    nka = jnp.where(swap, kb, ka)
    nkb = jnp.where(swap, ka, kb)
    nia = jnp.where(swap, ib, ia)
    nib = jnp.where(swap, ia, ib)
    kk = jnp.concatenate([nka, nkb], axis=1).reshape(B)
    ii = jnp.concatenate([nia, nib], axis=1).reshape(B)
    return kk, ii


@functools.lru_cache(maxsize=None)
def _sort_call(N: int, B: int, key_dtype: str, merge: bool,
               interpret: bool):
    """Cached pallas_call for the intra-block parts of the network.

    merge=False: the full local sort (global stages 0..log2(B)-1, every
    compare-exchange intra-block). merge=True: the trailing intra-block
    merge of ONE global stage s — distances B/2..1 after that stage's
    cross-block passes ran at the XLA level. The stage index rides as a
    scalar INPUT (it only feeds the direction shift), so one compiled
    kernel serves every merge stage of the array instead of one compile
    per stage."""
    pl = _pl()
    kd = jnp.dtype(key_dtype)
    lb = B.bit_length() - 1

    def local_kern(k_ref, i_ref, ok_ref, oi_ref):
        kk, ii = k_ref[:], i_ref[:]
        start = pl.program_id(0) * B
        for s in range(lb):
            for sub in range(s, -1, -1):
                kk, ii = _cmpex(kk, ii, 1 << sub, s, start)
        ok_ref[:] = kk
        oi_ref[:] = ii

    def merge_kern(s_ref, k_ref, i_ref, ok_ref, oi_ref):
        kk, ii = k_ref[:], i_ref[:]
        s = s_ref[0]
        start = pl.program_id(0) * B
        for sub in range(lb - 1, -1, -1):
            kk, ii = _cmpex(kk, ii, 1 << sub, s, start)
        ok_ref[:] = kk
        oi_ref[:] = ii

    blocked = _bspec((B,), lambda b: (b,))
    in_specs = [blocked, _bspec((B,), lambda b: (b,))]
    arg_specs = [jax.ShapeDtypeStruct((N,), kd),
                 jax.ShapeDtypeStruct((N,), _I32)]
    if merge:
        in_specs = [_bspec((1,), lambda b: (0,))] + in_specs
        arg_specs = [jax.ShapeDtypeStruct((1,), _I32)] + arg_specs
    call = pl.pallas_call(
        merge_kern if merge else local_kern,
        grid=(N // B,),
        in_specs=in_specs,
        out_specs=[_bspec((B,), lambda b: (b,)),
                   _bspec((B,), lambda b: (b,))],
        out_shape=[jax.ShapeDtypeStruct((N,), kd),
                   jax.ShapeDtypeStruct((N,), _I32)],
        interpret=interpret,
    )
    return _checked(
        f"sort_{'merge' if merge else 'local'}[N={N},B={B},{key_dtype}]",
        call, arg_specs)


@scoped
def sort_pairs(key: jax.Array, idx: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sort (key, idx) pairs ascending by the total order (key, idx).

    Drop-in for ``lax.sort((key, idx), num_keys=1, is_stable=True)`` when
    `idx` holds distinct values (the engine always passes an iota or a
    permutation): stability under ties == the (key, idx) lexicographic
    order. Keys must be integer-typed (the engine's packed/sentinel keys
    are). Non-power-of-two lengths pad with (dtype-max, n..N) sentinels
    that sort strictly after every real row, then slice back.
    """
    n = int(key.shape[0])
    if n <= 1:
        return key, idx
    assert jnp.issubdtype(key.dtype, jnp.integer), key.dtype
    _metrics.PALLAS_SORT_CALLS.inc()
    N = 1 << (n - 1).bit_length()
    B = min(SORT_BLOCK, N)
    k, i = key, idx.astype(_I32)
    if N != n:
        k = jnp.concatenate([
            k, jnp.full(N - n, jnp.iinfo(k.dtype).max, k.dtype)])
        i = jnp.concatenate([i, jnp.arange(n, N, dtype=_I32)])
    interp = _interpret()
    k, i = _sort_call(N, B, k.dtype.name, False, interp)(k, i)
    lb, lN = B.bit_length() - 1, N.bit_length() - 1
    for s in range(lb, lN):
        d = 1 << s
        while d >= B:
            # cross-block pass: pure elementwise compare at distance d —
            # XLA streams it at bandwidth; VMEM staging buys nothing here
            k, i = _cmpex(k, i, d, s, 0)
            d >>= 1
        k, i = _sort_call(N, B, k.dtype.name, True, interp)(
            jnp.full(1, s, _I32), k, i)
    if N != n:
        k, i = k[:n], i[:n]
    return k, i


# ---------------------------------------------------------------------------
# (b) fused group-by partial aggregation
# ---------------------------------------------------------------------------

def _seg_init(dtype, op: str):
    """The reduction identity ``jax.ops.segment_*`` leaves in EMPTY
    segments — +-inf for float min/max, iinfo extremes for ints — so the
    Pallas output is bit-identical even in slots no caller reads."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


@functools.lru_cache(maxsize=None)
def _seg_call(n_pad: int, tile: int, cap: int, specs: tuple,
              interpret: bool):
    """Cached pallas_call: specs is a static tuple of (dtype_name, op).
    One (cap x tile) membership mask per tile serves EVERY operand — the
    fused replacement for one scatter pass per aggregate."""
    pl = _pl()
    nd = len(specs)

    def kern(gid_ref, *refs):
        step = pl.program_id(0)
        g = gid_ref[:]
        seg = lax.broadcasted_iota(_I32, (cap, tile), 0)
        mask = g[None, :] == seg
        for j, (dt, op) in enumerate(specs):
            d_ref, o_ref = refs[j], refs[nd + j]
            init = _seg_init(jnp.dtype(dt), op)

            @pl.when(step == 0)
            def _(o_ref=o_ref, init=init):
                o_ref[:] = jnp.full((cap,), init)
            x = d_ref[:]
            if op == "sum":
                # pin the accumulator dtype: jnp.sum would promote i32 to
                # the platform int under x64, drifting off the output ref
                part = jnp.where(mask, x[None, :],
                                 jnp.zeros((), x.dtype)).sum(
                    axis=1, dtype=x.dtype)
                o_ref[:] = o_ref[:] + part
            else:
                fill = _seg_init(jnp.dtype(dt), op)
                red = jnp.min if op == "min" else jnp.max
                comb = jnp.minimum if op == "min" else jnp.maximum
                part = red(jnp.where(mask, x[None, :], fill), axis=1)
                o_ref[:] = comb(o_ref[:], part)

    blocked = _bspec((tile,), lambda b: (b,))
    call = pl.pallas_call(
        kern,
        grid=(n_pad // tile,),
        in_specs=[blocked] + [_bspec((tile,), lambda b: (b,))
                              for _ in specs],
        out_specs=[_bspec((cap,), lambda b: (0,)) for _ in specs],
        out_shape=[jax.ShapeDtypeStruct((cap,), jnp.dtype(dt))
                   for dt, _ in specs],
        interpret=interpret,
    )
    return _checked(
        f"seg_reduce[n={n_pad},tile={tile},segments={cap},"
        f"{'+'.join(f'{op}:{dt}' for dt, op in specs)}]", call,
        [jax.ShapeDtypeStruct((n_pad,), _I32)] +
        [jax.ShapeDtypeStruct((n_pad,), jnp.dtype(dt)) for dt, _ in specs])


def seg_supported(data: jax.Array, num_segments: int, op: str) -> bool:
    """Static eligibility for one operand: bounded segment count (the
    membership mask is VMEM-resident) and order-independent math only —
    integer sums and any-dtype min/max are exact in every order, float
    sums are not (they keep the XLA path so flag-off stays bit-identical).
    """
    if not (1 <= num_segments <= GROUPBY_MAX_SEGMENTS):
        return False
    if data.ndim != 1 or data.dtype == jnp.bool_:
        return False
    if op == "sum":
        return bool(jnp.issubdtype(data.dtype, jnp.integer))
    return op in ("min", "max")


@scoped
def seg_reduce_multi(operands: list, gid: jax.Array,
                     num_segments: int) -> list:
    """Fused segment partials: operands is [(data, op)] with every entry
    ``seg_supported``; one kernel pass computes them all. Rows whose gid
    falls outside [0, num_segments) contribute nothing (the engine's
    dead-row sentinel convention, same as segment_sum's out-of-range
    drop)."""
    _metrics.PALLAS_GROUPBY_CALLS.inc()
    n = int(gid.shape[0])
    tile = GROUPBY_MASK_ELEMS // max(1, num_segments)
    tile = 1 << min(GROUPBY_MAX_TILE.bit_length() - 1,
                    max(0, tile.bit_length() - 1))     # pow2, <= max tile
    tile = min(tile, 1 << max(0, (n - 1).bit_length()))
    n_pad = -(-n // tile) * tile
    g = gid.astype(_I32)
    datas = [d for d, _ in operands]
    if n_pad != n:
        g = jnp.concatenate([
            g, jnp.full(n_pad - n, num_segments, _I32)])
        datas = [jnp.concatenate([d, jnp.zeros(n_pad - n, d.dtype)])
                 for d in datas]
    specs = tuple((d.dtype.name, op) for d, (_, op) in zip(datas, operands))
    call = _seg_call(n_pad, tile, num_segments, specs, _interpret())
    out = call(g, *datas)
    return list(out)


@scoped
def seg_reduce(data: jax.Array, gid: jax.Array, num_segments: int,
               op: str) -> jax.Array:
    """Single-operand convenience over ``seg_reduce_multi``."""
    return seg_reduce_multi([(data, op)], gid, num_segments)[0]


# ---------------------------------------------------------------------------
# (c) batched multi-column gather
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _gather_call(n_pad: int, blk: int, src_specs: tuple, interpret: bool):
    """Cached pallas_call: src_specs is a static tuple of (rows, dtype
    name). Sources stage whole in VMEM (index maps pin block 0), index
    tiles stream through."""
    pl = _pl()

    def kern(idx_ref, *refs):
        nd = len(src_specs)
        iv = idx_ref[:]
        for j in range(nd):
            refs[nd + j][:] = refs[j][iv]

    in_specs = [_bspec((blk,), lambda b: (b,))]
    in_specs += [_bspec((rows,), lambda b: (0,)) for rows, _ in src_specs]
    call = pl.pallas_call(
        kern,
        grid=(n_pad // blk,),
        in_specs=in_specs,
        out_specs=[_bspec((blk,), lambda b: (b,)) for _ in src_specs],
        out_shape=[jax.ShapeDtypeStruct((n_pad,), jnp.dtype(dt))
                   for _, dt in src_specs],
        interpret=interpret,
    )
    return _checked(
        f"gather[n={n_pad},blk={blk},"
        f"{'+'.join(f'{dt}[{rows}]' for rows, dt in src_specs)}]", call,
        [jax.ShapeDtypeStruct((n_pad,), _I32)] +
        [jax.ShapeDtypeStruct((rows,), jnp.dtype(dt))
         for rows, dt in src_specs])


def _src_bytes(src: jax.Array) -> int:
    return int(src.shape[0]) * src.dtype.itemsize


def gather_supported(src: jax.Array) -> bool:
    """One source column is VMEM-stageable: 1-D and within the budget."""
    return src.ndim == 1 and src.shape[0] >= 1 and \
        _src_bytes(src) <= GATHER_SRC_BYTES


@scoped
def take_many(srcs: list, idx: jax.Array) -> list:
    """Gather ``[src[idx] for src in srcs]`` with VMEM-staged sources.

    Columns batch greedily into kernel calls under the VMEM budget (one
    index-tile pass serves the whole batch — the late-mat attribute-join
    shape gathers every dimension attribute with ONE index vector).
    Columns too large to stage fall back to the XLA gather individually;
    gather is a permutation read, so the mix is bit-identical."""
    n = int(idx.shape[0])
    out: list = [None] * len(srcs)
    todo: list[int] = []
    for j, s in enumerate(srcs):
        if gather_supported(s) and n >= 1:
            todo.append(j)
        else:
            out[j] = s[idx]
    if not todo:
        return out
    _metrics.PALLAS_GATHER_CALLS.inc()
    blk = min(GATHER_BLOCK, max(1, n))
    n_pad = -(-n // blk) * blk
    iv = idx.astype(_I32)
    if n_pad != n:
        iv = jnp.concatenate([iv, jnp.zeros(n_pad - n, _I32)])
    interp = _interpret()
    batch: list[int] = []
    budget = 0

    def flush(batch):
        arrs = []
        for j in batch:
            s = srcs[j]
            arrs.append(s.astype(jnp.uint8) if s.dtype == jnp.bool_ else s)
        specs = tuple((int(a.shape[0]), a.dtype.name) for a in arrs)
        res = _gather_call(n_pad, blk, specs, interp)(iv, *arrs)
        for j, r in zip(batch, res):
            r = r[:n] if n_pad != n else r
            out[j] = r.astype(bool) if srcs[j].dtype == jnp.bool_ else r

    for j in todo:
        b = _src_bytes(srcs[j])
        if batch and budget + b > GATHER_SRC_BYTES:
            flush(batch)
            batch, budget = [], 0
        batch.append(j)
        budget += b
    if batch:
        flush(batch)
    return out


@scoped
def take(src: jax.Array, idx: jax.Array) -> jax.Array:
    """Single-column convenience over ``take_many``."""
    return take_many([src], idx)[0]
