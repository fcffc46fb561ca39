"""Distributed relational primitives over a device mesh (shard_map + collectives).

Spark-shuffle analogs, TPU-native (SURVEY.md §2 last row):
- `repartition_by_key`   all_to_all hash shuffle of row blocks
- `broadcast_join_aggregate`  replicated build side (all_gather-free: the
  dimension table is small, so it rides in replicated sharding), sharded
  probe side, local partial aggregation, psum merge — the classic
  "broadcast join + partial agg" Spark plan for star-schema queries.
- `distributed_aggregate`  local partial agg -> all_gather of bounded
  partials -> replicated final merge (Spark partial/final aggregate).

Everything is a single jittable SPMD program: static shapes, masked rows,
collectives inserted explicitly via shard_map.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.jax_backend import kernels

_I32 = jnp.int32


def shard_rows(arrays: list[jax.Array], alive: jax.Array, mesh: Mesh
               ) -> tuple[list[jax.Array], jax.Array]:
    """Pad row count to a multiple of the mesh size and row-shard everything."""
    n_shards = mesh.devices.size
    axis = mesh.axis_names[0]
    cap = int(alive.shape[0])
    padded = ((cap + n_shards - 1) // n_shards) * n_shards
    sharding = NamedSharding(mesh, P(axis))

    def pad(x):
        if x.shape[0] != padded:
            fill = jnp.zeros((padded - x.shape[0],) + x.shape[1:], x.dtype)
            x = jnp.concatenate([x, fill])
        return jax.device_put(x, sharding)

    return [pad(a) for a in arrays], pad(alive)


def _multi_hash(keys: list[jax.Array], n_shards: int) -> jax.Array:
    """Shard assignment over a composite key (mix-fold each column)."""
    h = jnp.zeros(keys[0].shape, jnp.uint32)
    for k in keys:
        h = h * jnp.uint32(1000003) + (k.astype(jnp.uint32)
                                       * jnp.uint32(2654435761) >> 13)
    return (h % jnp.uint32(n_shards)).astype(_I32)


def _as_key_list(key) -> list[jax.Array]:
    return list(key) if isinstance(key, (list, tuple)) else [key]


def repartition_by_key(mesh: Mesh, per_pair_capacity: int,
                       emit_key: bool = True):
    """Build a jittable all_to_all hash-repartition over `mesh`.

    Returned fn maps (columns, alive, key) — all row-sharded — to the same
    pytree with every row now living on shard hash(key) % n_shards, plus an
    int32 overflow counter (rows dropped because a (src,dst) block exceeded
    per_pair_capacity; callers must size capacity so this stays 0).
    `key` may be one array or a list of arrays (composite shuffle key: the
    hash mixes every column, the returned key is the first).
    emit_key=False skips the separate exchanged key output (the alive mask
    is returned in its slot) — join lowering already carries the key inside
    `columns`, and the duplicate would cross the ICI once per run.
    """
    axis = mesh.axis_names[0]
    n_shards = mesh.devices.size

    def local(cols, alive, key):
        keys = _as_key_list(key)
        cap = alive.shape[0]
        dest = jnp.where(alive, _multi_hash(keys, n_shards), n_shards)
        key = keys[0]
        # rank of each row within its destination block
        order = jnp.argsort(dest, stable=True)
        dest_sorted = dest[order]
        boundary = jnp.concatenate(
            [jnp.ones(1, bool), dest_sorted[1:] != dest_sorted[:-1]])
        pos_in_block = jnp.arange(cap, dtype=_I32) - \
            lax.cummax(jnp.where(boundary, jnp.arange(cap, dtype=_I32), 0),
                       axis=0)
        slot_sorted = pos_in_block
        overflow = jnp.sum((slot_sorted >= per_pair_capacity) &
                           (dest_sorted < n_shards)).astype(_I32)
        # scatter rows into [n_shards, per_pair_capacity] blocks
        ok = (slot_sorted < per_pair_capacity) & (dest_sorted < n_shards)
        flat = jnp.where(ok, dest_sorted * per_pair_capacity + slot_sorted,
                         n_shards * per_pair_capacity)

        def place(col_sorted):
            buf = jnp.zeros((n_shards * per_pair_capacity + 1,),
                            col_sorted.dtype)
            zero = jnp.zeros((), col_sorted.dtype)   # keep bool cols bool
            return buf.at[flat].set(jnp.where(ok, col_sorted, zero)
                                    )[:n_shards * per_pair_capacity]

        out_cols = [place(c[order]) for c in cols]
        out_alive = jnp.zeros(n_shards * per_pair_capacity + 1, bool).at[
            flat].set(ok)[:n_shards * per_pair_capacity]
        out_key = place(key[order]) if emit_key else out_alive
        # exchange: block b of this shard -> shard b
        def exchange(x):
            blocks = x.reshape((n_shards, per_pair_capacity) + x.shape[1:])
            return lax.all_to_all(blocks, axis, split_axis=0, concat_axis=0
                                  ).reshape((-1,) + x.shape[1:])
        out_cols = [exchange(c) for c in out_cols]
        out_alive = exchange(out_alive)
        out_key = exchange(out_key) if emit_key else out_alive
        overflow = lax.psum(overflow, axis)
        return out_cols, out_alive, out_key, overflow

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis)),
                     out_specs=(P(axis), P(axis), P(axis), P()))


def gather_partials(mesh: Mesh):
    """Jittable all_gather of a row-sharded pytree of per-replica partial
    blocks into a replicated concatenation (tiled: shard k's rows land at
    block k). The engine's sharded morsel path dispatches this as its ONE
    collective per morsel: device-local partial aggregates are bounded
    (group-cardinality-sized), so only the decomposed partials ride the
    ICI before the existing host-side final merge
    (jax_backend/shard_exec.ShardedMorselQuery)."""
    axis = mesh.axis_names[0]

    def local(tree):
        return jax.tree_util.tree_map(
            lambda x: lax.all_gather(x, axis, tiled=True), tree)

    return shard_map(local, mesh=mesh, in_specs=(P(axis),), out_specs=P(),
                     check_vma=False)


def _local_join_ranges(lkd, lal, rkd, ral):
    """Per-shard probe ranges for a co-partitioned join block (the generic
    sort-based machinery, shard-local): returns (lo, cnt, perm_r)."""
    lcap, rcap = lal.shape[0], ral.shape[0]
    kd = [jnp.concatenate([a, b]) for a, b in zip(lkd, rkd)]
    al = jnp.concatenate([lal, ral])
    gid, _ = kernels.dense_rank(
        kd, [jnp.ones(lcap + rcap, bool)] * len(kd), al)
    l_gid, r_gid = gid[:lcap], gid[lcap:]
    _, perm_r = kernels.build_side(
        jnp.where(al[lcap:], r_gid, jnp.iinfo(_I32).max), ral)
    lo, cnt = kernels.probe_counts_by_gid(r_gid, ral, l_gid, lal,
                                          gid_cap=lcap + rcap)
    return lo, cnt, perm_r


def shuffle_join_counts(mesh: Mesh):
    """Jittable per-shard probe ranges + match totals of a co-partitioned
    (repartitioned) join: (lkeys, lalive, rkeys, ralive) -> ((n_shards,)
    counts, lo, cnt, perm_r) — the ranges feed shuffle_join_expand so the
    dominant per-shard sort happens ONCE."""
    axis = mesh.axis_names[0]

    def local(lkd, lal, rkd, ral):
        lo, cnt, perm_r = _local_join_ranges(list(lkd), lal, list(rkd), ral)
        return jnp.sum(cnt).reshape(1), lo, cnt, perm_r

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis), P(axis)),
                     out_specs=(P(axis),) * 4, check_vma=False)


def shuffle_join_expand(mesh: Mesh, cap_out_shard: int):
    """Jittable shard-local inner-join expansion over co-partitioned sides,
    reusing the probe ranges from shuffle_join_counts.

    (lo, cnt, perm_r, lalive, lcols, rcols) -> (out_lcols, out_rcols,
    out_alive), each sharded with cap_out_shard rows per shard. Together
    with repartition_by_key this is the Spark partitioned shuffle join
    (SURVEY.md §2 parallelism table last row): only hash-routed blocks ride
    the ICI — the fact sides are never gathered."""
    axis = mesh.axis_names[0]

    def local(lo, cnt, perm_r, lal, lcols, rcols):
        rcap = perm_r.shape[0]
        left_idx, build_pos, alive_out = kernels.expand_join(
            lo, cnt, lal, cap_out_shard)
        right_rows = perm_r[jnp.clip(build_pos, 0, rcap - 1)]
        out_l = tuple(c[left_idx] for c in lcols)
        out_r = tuple(c[right_rows] for c in rcols)
        return out_l, out_r, alive_out

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis),) * 6,
                     out_specs=(P(axis), P(axis), P(axis)), check_vma=False)


def _partial_agg(spec: str, v, contrib, gid, n_partial):
    sg = jnp.where(contrib, gid, n_partial)
    if spec == "count":
        return jax.ops.segment_sum(jnp.where(contrib, 1, 0).astype(v.dtype),
                                   sg, num_segments=n_partial)
    if spec == "sum":
        return jax.ops.segment_sum(jnp.where(contrib, v, 0), sg,
                                   num_segments=n_partial)
    if spec in ("min", "max"):
        ext = kernels._extreme(v.dtype, spec)
        seg = jax.ops.segment_min if spec == "min" else jax.ops.segment_max
        return seg(jnp.where(contrib, v, ext), sg, num_segments=n_partial)
    raise ValueError(spec)


def _merge_agg(spec: str, p, g_alive, m_gid, cap_out):
    sg = jnp.where(g_alive, m_gid, cap_out)
    if spec in ("sum", "count"):
        return jax.ops.segment_sum(jnp.where(g_alive, p, 0), sg,
                                   num_segments=cap_out)
    ext = kernels._extreme(p.dtype, spec)
    seg = jax.ops.segment_min if spec == "min" else jax.ops.segment_max
    return seg(jnp.where(g_alive, p, ext), sg, num_segments=cap_out)


def distributed_aggregate(mesh: Mesh, n_partial: int, specs: list[str],
                          n_keys: int = 1):
    """Partial-aggregate per shard, all_gather bounded partials, final merge.

    specs: per-value aggregation kind, "sum"|"count"|"min"|"max".
    Returned jittable fn: (group_keys [sharded; one array or a list of
    n_keys arrays — composite GROUP BY], valid (same shape), alive, values)
    -> (group_keys, key_valids [False marks a NULL group key — the key
    array's raw value is meaningless there], agg_values, out_alive,
    overflow) replicated, n_partial * n_shards rows each; overflow counts
    rows in groups beyond n_partial (callers must size n_partial so it
    stays 0 — otherwise results are partial). Single-key callers get single
    key/valid arrays back.
    """
    axis = mesh.axis_names[0]

    def local(keys, valids, alive, values):
        keys, valids = _as_key_list(keys), _as_key_list(valids)
        single = len(keys) == 1
        gid, _ = kernels.dense_rank(keys, valids, alive)
        cap = alive.shape[0]
        # rows in groups beyond the partial capacity would be silently
        # dropped by the out-of-range scatter — count them instead
        overflow = jnp.sum((alive & (gid >= n_partial) & (gid < cap))
                           .astype(_I32))
        reps, rep_valids = [], []
        for k, kv in zip(keys, valids):
            r, rv = kernels.group_representatives(gid, alive, k, kv,
                                                  n_partial)
            reps.append(r)
            rep_valids.append(rv)
        # slot occupancy is "some alive row landed here" — NOT any key's
        # validity (a group whose first GROUP BY key is NULL still exists)
        occ = jnp.zeros(n_partial + 1, bool).at[
            jnp.where(alive & (gid < n_partial), gid, n_partial)
        ].set(True)[:n_partial]
        contrib = alive
        partials = [_partial_agg(spec, v, contrib, gid, n_partial)
                    for spec, v in zip(specs, values)]
        # gather all shards' partials everywhere, merge locally (replicated)
        g_keys = [lax.all_gather(r, axis, tiled=True) for r in reps]
        g_valids = [lax.all_gather(rv, axis, tiled=True)
                    for rv in rep_valids]
        g_occ = lax.all_gather(occ, axis, tiled=True)
        g_partials = [lax.all_gather(p, axis, tiled=True) for p in partials]
        m_gid, _ = kernels.dense_rank(g_keys, g_valids, g_occ)
        cap_out = g_keys[0].shape[0]
        out_keys, out_valids = [], []
        for gk, gv in zip(g_keys, g_valids):
            ok, ov = kernels.group_representatives(m_gid, g_occ, gk, gv,
                                                   cap_out)
            out_keys.append(ok)
            out_valids.append(ov)
        out_alive = jnp.zeros(cap_out + 1, bool).at[
            jnp.where(g_occ, m_gid, cap_out)].set(True)[:cap_out]
        merged = [_merge_agg(spec, p, g_occ, m_gid, cap_out)
                  for spec, p in zip(specs, g_partials)]
        keys_out = out_keys[0] if single else out_keys
        valids_out = out_valids[0] if single else out_valids
        return keys_out, valids_out, merged, out_alive, \
            lax.psum(overflow, axis)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis), P(axis)),
                     out_specs=(P(), P(), P(), P(), P()), check_vma=False)


def broadcast_join_aggregate(mesh: Mesh, n_partial: int, specs: list[str]):
    """The flagship star-schema step as ONE SPMD program.

    Sharded fact side (probe), replicated dimension side (build, unique
    keys assumed — PK side), filter mask applied, inner-join semantics,
    grouped partial aggregation by one or more dimension attributes,
    psum-free all_gather merge. This is the TPU-native shape of NDS
    power-run queries (fact x dims -> group -> agg; e.g. reference query
    templates joining store_sales to date_dim/item, SURVEY.md §0).

    specs: per-value "sum"|"count"|"min"|"max".
    Returned jittable fn:
      (fact_key, fact_mask, fact_alive, fact_values,
       dim_key, dim_group [one array or a list — composite GROUP BY],
       dim_alive) ->
      (group_keys, agg_values, out_alive, overflow) replicated; overflow
      counts rows in groups beyond n_partial (must be 0 for exact results).
    """
    axis = mesh.axis_names[0]

    def local(fact_key, fact_mask, fact_alive, fact_values,
              dim_key, dim_group, dim_alive):
        groups = _as_key_list(dim_group)
        single = not isinstance(dim_group, (list, tuple))
        alive = fact_alive & fact_mask
        # build: sort replicated dim keys once (same on every shard)
        rcap = dim_key.shape[0]
        bkey = jnp.where(dim_alive, dim_key, jnp.iinfo(fact_key.dtype).max)
        sorted_key, perm = lax.sort((bkey, jnp.arange(rcap, dtype=_I32)),
                                    num_keys=1, is_stable=True)
        idx = jnp.searchsorted(sorted_key, fact_key)
        idx = jnp.clip(idx, 0, rcap - 1)
        matched = (sorted_key[idx] == fact_key) & alive
        grps = [g[perm[idx]] for g in groups]
        gid, _ = kernels.dense_rank(grps, [matched] * len(grps), matched)
        cap = matched.shape[0]
        overflow = jnp.sum((matched & (gid >= n_partial) & (gid < cap))
                           .astype(_I32))
        reps, rep_alive = [], None
        for grp in grps:
            r, ra = kernels.group_representatives(gid, matched, grp,
                                                  matched, n_partial)
            reps.append(r)
            rep_alive = ra if rep_alive is None else rep_alive
        partials = [_partial_agg(spec, v, matched, gid, n_partial)
                    for spec, v in zip(specs, fact_values)]
        g_keys = [lax.all_gather(r, axis, tiled=True) for r in reps]
        g_alive = lax.all_gather(rep_alive, axis, tiled=True)
        g_partials = [lax.all_gather(p, axis, tiled=True) for p in partials]
        m_gid, _ = kernels.dense_rank(g_keys, [g_alive] * len(g_keys),
                                      g_alive)
        cap_out = g_keys[0].shape[0]
        out_keys, out_alive = [], None
        for gk in g_keys:
            ok, oa = kernels.group_representatives(m_gid, g_alive, gk,
                                                   g_alive, cap_out)
            out_keys.append(ok)
            out_alive = oa
        merged = [_merge_agg(spec, p, g_alive, m_gid, cap_out)
                  for spec, p in zip(specs, g_partials)]
        keys_out = out_keys[0] if single else out_keys
        return keys_out, merged, out_alive, lax.psum(overflow, axis)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(axis), P(axis), P(axis), P(axis),
                               P(), P(), P()),
                     out_specs=(P(), P(), P(), P()), check_vma=False)
