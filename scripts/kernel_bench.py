"""Per-kernel XLA-vs-Pallas microbenchmark (promotes scripts/exp_gather.py).

Times the three ISSUE-7 kernel families — tiled segmented sort, fused
group-by partial aggregation, batched multi-column gather — against their
generic XLA lowerings over a rows x dtype grid (the clock stops after
block_until_ready). Every record carries its best wall and, on a device
with published peaks, the share of the HBM bandwidth its analytic bytes
reach over that wall. Each Pallas kernel's outcome is recorded on its own: "ok" with its
result checked bit-identical to the XLA lowering's, or "refused" with the
compiler's message (PallasLoweringError) — one refusal does not end the
run.

Stdlib argparse only; run on a TPU for compiled Mosaic outcomes/numbers, or
under JAX_PLATFORMS=cpu for interpret-mode code-path checks (whose times
are not device numbers and carry no roofline):

    python scripts/kernel_bench.py --rows 65536,262144 --dtypes int32,int64
    python scripts/kernel_bench.py --kernels gather --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="kernel_bench.py",
        description="XLA vs Pallas microbench per relational kernel "
                    "(per-kernel compile outcome, best wall, roofline "
                    "share)")
    p.add_argument("--kernels", default="sort,groupby,gather",
                   help="comma subset of sort,groupby,gather")
    p.add_argument("--rows", default="65536,262144",
                   help="comma list of row counts")
    p.add_argument("--dtypes", default="int32,int64",
                   help="comma list of payload dtypes (int32,int64)")
    p.add_argument("--segments", type=int, default=1024,
                   help="group count for the groupby kernel")
    p.add_argument("--src_rows", type=int, default=1 << 18,
                   help="gather source-table rows (VMEM-staged)")
    p.add_argument("--gather_cols", type=int, default=4,
                   help="columns gathered per index vector")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--no_x64", action="store_true",
                   help="keep 32-bit jax types (default enables x64, the "
                        "engine's measured configuration)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON line per measurement instead of the "
                        "fixed-width table")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = _parse_args(argv)
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    import jax
    import jax.numpy as jnp
    import numpy as np

    if not args.no_x64:
        jax.config.update("jax_enable_x64", True)
    from nds_tpu.engine.jax_backend import pallas_kernels as pk
    from nds_tpu.obs.device_time import roofline_bw_gbps
    from nds_tpu.report import device_capture

    device = device_capture()
    bw_gbps = roofline_bw_gbps(device)
    mode, reason = pk.probe()
    if mode == "off":
        print(f"pallas unavailable: {reason} (XLA rows still measured)",
              file=sys.stderr)
    pk.set_active(pk.parse_ops(args.kernels) if mode != "off"
                  else frozenset())
    kernels = [k.strip() for k in args.kernels.split(",") if k.strip()]
    rows_grid = [int(r) for r in args.rows.split(",") if r]
    dtypes = [d.strip() for d in args.dtypes.split(",") if d.strip()]
    rng = np.random.default_rng(778)
    records: list[dict] = []

    def timed_call(fn, *call_args):
        """(wall ms, host result) of one call; the clock stops only after
        block_until_ready, the host copy is taken outside it."""
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*call_args))
        ms = (time.perf_counter() - t0) * 1000.0
        return ms, jax.device_get(out)

    def run_pair(name: str, n: int, dt: str, xla_fn, pallas_fn,
                 bytes_accessed: float, args_):
        want = None
        for impl, fn in (("xla", xla_fn), ("pallas", pallas_fn)):
            if fn is None:
                continue
            rec = {"kernel": name, "impl": impl, "rows": n, "dtype": dt,
                   "mode": mode if impl == "pallas" else "xla",
                   "device": device}
            records.append(rec)
            jfn = jax.jit(fn)
            try:
                for _ in range(max(1, args.warmup)):
                    _ms, host = timed_call(jfn, *args_)
            except pk.PallasLoweringError as e:
                rec.update(status="refused", error=str(e)[:2000])
                continue
            best = float("inf")
            for _ in range(max(1, args.iters)):
                ms, host = timed_call(jfn, *args_)
                best = min(best, ms)
            rec.update(status="ok", best_ms=round(best, 3))
            if bw_gbps and best > 0:
                # the analytic bytes of the call over its best wall, as a
                # share of the device's published bandwidth
                rec["roofline_frac"] = round(
                    bytes_accessed / (bw_gbps * 1e9) / (best / 1e3), 5)
            if impl == "xla":
                want = host
            elif want is not None:
                rec["bit_identical_to_xla"] = all(
                    np.array_equal(a, b) for a, b in zip(
                        jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(host)))

    for dt in dtypes:
        jdt = jnp.dtype(dt)
        for n in rows_grid:
            key = jnp.asarray(rng.integers(0, 1 << 30, n), jdt)
            iota = jnp.arange(n, dtype=jnp.int32)
            if "sort" in kernels:
                from jax import lax
                run_pair(
                    f"sort[{dt},{n}]", n, dt,
                    lambda k, i: lax.sort((k, i), num_keys=1,
                                          is_stable=True),
                    (lambda k, i: pk.sort_pairs(k, i))
                    if mode != "off" else None,
                    # one read + one write of both operands per merge pass
                    2.0 * (key.nbytes + iota.nbytes) *
                    max(1, n.bit_length() - 1),
                    (key, iota))
            if "groupby" in kernels:
                S = args.segments
                gid = jnp.asarray(rng.integers(0, S, n), jnp.int32)
                data = jnp.asarray(rng.integers(0, 1000, n), jdt)

                def xla_gb(g, d, S=S):
                    return (jax.ops.segment_sum(d, g, num_segments=S),
                            jax.ops.segment_min(d, g, num_segments=S),
                            jax.ops.segment_max(d, g, num_segments=S))

                def pallas_gb(g, d, S=S):
                    return tuple(pk.seg_reduce_multi(
                        [(d, "sum"), (d, "min"), (d, "max")], g, S))

                run_pair(f"groupby[{dt},{n},S={S}]", n, dt, xla_gb,
                         pallas_gb if mode != "off" else None,
                         float(gid.nbytes + 3 * data.nbytes), (gid, data))
            if "gather" in kernels:
                srcs = [jnp.asarray(rng.integers(0, 1 << 30, args.src_rows),
                                    jdt) for _ in range(args.gather_cols)]
                idx = jnp.asarray(rng.integers(0, args.src_rows, n),
                                  jnp.int32)

                def xla_ga(i, *ss):
                    return tuple(s[i] for s in ss)

                def pallas_ga(i, *ss):
                    return tuple(pk.take_many(list(ss), i))

                run_pair(f"gather[{dt},{n}x{args.gather_cols}]", n, dt,
                         xla_ga, pallas_ga if mode != "off" else None,
                         float(idx.nbytes +
                               sum(s.nbytes for s in srcs) +
                               args.gather_cols * n * jdt.itemsize),
                         (idx, *srcs))

    if args.json:
        for r in records:
            print(json.dumps(r))
    else:
        print(f"device: {device}; pallas mode: {mode}"
              + (f" ({reason})" if reason else ""))
        for r in records:
            if r["impl"] == "pallas":
                print(f"{r['kernel']}: {r['status']}"
                      + (f", bit-identical to xla: "
                         f"{r.get('bit_identical_to_xla')}"
                         if r["status"] == "ok" else f": {r['error']}"))
        for r in records:
            if r["status"] == "ok":
                rf = r.get("roofline_frac")
                print(f"kernel/{r['kernel']}:{r['impl']:<7} best "
                      f"{r['best_ms']:>9.3f} ms  roofline "
                      f"{f'{rf:.4f}' if rf is not None else '-'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
