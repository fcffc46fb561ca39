"""Multi-chip sharded morsel execution (ISSUE 8 / ROADMAP item 2).

The streaming path used to run every per-morsel program on one chip even
when a mesh was available. Here each ScanGroup's morsel stream partitions
across data-parallel replicas of the device mesh ("shards" axis,
parallel/mesh.make_mesh):

- `stage_sharded` packs one morsel as n equal per-replica payload blocks
  (narrow-lane PackedTable wire format included) and uploads the
  concatenation in a SINGLE device_put with NamedSharding(P("shards")) —
  the flat uint8 buffer divides evenly, so replica k's device slice is
  exactly row block k's packed bytes. Unpackable layouts fall back to a
  per-leaf row-sharded DTable upload.
- `ShardedMorselQuery` is the sharded analog of executor.CompiledQuery:
  every replica replays the SAME recorded capacity schedule over its local
  rows via shard_map (a shard-local JaxExecutor — no in-plan collectives,
  the shard_map boundary is the collective), producing device-local
  partial aggregates. A second compiled program — dist_ops.gather_partials
  — is the morsel's ONE collective: a tiled all_gather of the bounded
  decomposed partials, with a span of its own (`collective`, labelled
  `<query>/gather:<table>@mesh<n>`) and a module of its own on the device
  trace (`jit_nds_<query>_morsel_<table>_gather` beside `..._local`), so
  collective time and bytes are first-class numbers. The `collective` span
  covers the gather program alone: its dispatch and, while the tracer is
  on, the wait for its result. The gathered partials' copy to the host is
  an `exec.fetch` span after it, as on the one-chip path. Untraced,
  nothing waits between the two, so `ExecStats.collective_ms` stays the
  wall from the gather's dispatch to its result on the host.

The host-side final merge is unchanged: gathered per-replica partials are
just more rows of the same partial schema streaming's _decompose /
_final_builder already merge across morsels, so results are bit-identical
to the single-chip path for order-independent (integer/decimal) partials —
the measured exact-decimal bench configuration.

Spark frame (SURVEY.md §2): replicas play the executors, the morsel
row-shard plays maxPartitionBytes input splits, and the partial gather
plays the partial/final aggregate exchange.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...obs import metrics as _metrics
from ...obs.trace import TRACER
from ...parallel.dist_ops import gather_partials
from ..column import Table
from ..streaming import partition_morsel_rows
from .device import (DTable, PackedTable, _pack_payload, bucket,
                     plan_lanes)
from .executor import (JaxExecutor, ReplayMismatch, _named, _no_load,
                       _Recorder, count_fetched, count_join_paths,
                       program_name)


# -- sharded morsel staging ---------------------------------------------------

def stage_sharded(table: Table, mesh, shard_cap: int,
                  lanes: Optional[tuple] = None,
                  encs: Optional[tuple] = None,
                  codebooks: Optional[tuple] = None):
    """Pack + upload one morsel row-sharded over `mesh`: per-replica row
    blocks (streaming.partition_morsel_rows) each packed at `shard_cap`
    capacity, concatenated, and committed with ONE device_put under
    NamedSharding(P("shards")). Returns a PackedTable whose `cap` is the
    PER-REPLICA capacity — inside the shard_map body each replica sees its
    own payload slice, so unpack_table yields that replica's rows. Encoded
    execution rides along unchanged: each replica block packs under the
    SAME static encoding spec (dict codes / rle pairs), so block payloads
    stay equal-length and the flat buffer still divides evenly. Falls
    back to a row-sharded plain DTable when the layout cannot pack."""
    n_shards = mesh.devices.size
    axis = mesh.axis_names[0]
    sharding = NamedSharding(mesh, P(axis))
    spans = partition_morsel_rows(table.num_rows, n_shards)
    if lanes is None:
        lanes = plan_lanes([c.dtype for c in table.columns], narrow=False)
    x64 = jax.config.read("jax_enable_x64")
    packable = lanes is not None and (
        x64 or not any(ln in ("i64", "f64") for ln in lanes))
    from ...obs.profile import DEVICE_MEM
    from .device import _mem_leaves
    with TRACER.span("morsel.stage_sharded", cat="upload",
                     rows=table.num_rows, shards=n_shards,
                     capacity=shard_cap * n_shards):
        if packable:
            payloads = []
            dicts: list = []
            for lo, hi in spans:
                payload, dicts = _pack_payload(table.slice(lo, hi),
                                               tuple(lanes), hi - lo,
                                               shard_cap, encs, codebooks)
                payloads.append(payload)
            flat = np.concatenate(payloads)
            data = jax.device_put(flat, sharding)
            out = PackedTable(list(table.names),
                              [c.dtype for c in table.columns],
                              tuple(lanes), shard_cap, data, tuple(dicts),
                              tuple(encs) if encs else (),
                              tuple(codebooks) if codebooks else ())
        else:
            out = _sharded_dtable(table, spans, shard_cap, sharding)
    DEVICE_MEM.add(_mem_leaves(out))
    return out


def _sharded_dtable(table: Table, spans, shard_cap: int,
                    sharding) -> DTable:
    """Wide fallback: per-replica row blocks laid out contiguously in each
    column buffer (block k at offset k * shard_cap), every leaf committed
    row-sharded in one device_put of the whole pytree."""
    n_shards = len(spans)
    from .device import DCol, phys_dtype
    cols_np = []
    for c in table.columns:
        data = np.asarray(c.data)
        dt = np.dtype(phys_dtype(c.dtype))
        buf = np.zeros(shard_cap * n_shards, dtype=dt)
        vbuf = np.zeros(shard_cap * n_shards, dtype=bool)
        for k, (lo, hi) in enumerate(spans):
            m = hi - lo
            if not m:
                continue
            v = c.validity[lo:hi]
            block = np.where(v, data[lo:hi], 0)
            if c.dtype == "str":
                block = np.where(v & (data[lo:hi] >= 0), data[lo:hi], 0)
            buf[k * shard_cap:k * shard_cap + m] = block
            vbuf[k * shard_cap:k * shard_cap + m] = v
        cols_np.append((buf, vbuf))
    alive = np.zeros(shard_cap * n_shards, dtype=bool)
    for k, (lo, hi) in enumerate(spans):
        alive[k * shard_cap:k * shard_cap + (hi - lo)] = True
    dt = DTable(list(table.names),
                [DCol(c.dtype, buf, vbuf, c.dictionary)
                 for c, (buf, vbuf) in zip(table.columns, cols_np)],
                alive)
    return jax.device_put(dt, sharding)


# -- sharded per-morsel program ----------------------------------------------

class ShardedMorselQuery:
    """One recorded per-morsel schedule replayed on every mesh replica.

    plan may be a list (shared-scan fused group: one multi-output program,
    one shared decision schedule) exactly like CompiledQuery. Two compiled
    programs per instance:

    - the LOCAL program: shard_map over the row-sharded morsel + replicated
      dimension scans; each replica traces the plan(s) through a
      shard-local replay JaxExecutor and returns its partial-aggregate
      block(s), still sharded, plus per-replica schedule-check scalars;
    - the GATHER program (dist_ops.gather_partials): the morsel's single
      collective — tiled all_gather of the bounded partials, so the fetched
      result is the concatenation of every replica's block.

    Schedule verification is shard-aware: capacity checks take the max over
    replicas (<= planned bucket), exact checks must agree on every replica
    (shard-local recording keeps them data-independent). A genuine overflow
    raises ReplayMismatch and the session re-records that morsel eagerly on
    one chip — correctness never depends on the recorded bound."""

    def __init__(self, plan, decisions: list, scan_keys: tuple, mesh,
                 morsel_key: str, label: str = "",
                 name_fingerprint: Optional[str] = None):
        self.plan = plan
        self.decisions = decisions
        self.scan_keys = tuple(scan_keys)
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        self.morsel_key = morsel_key
        base = label or "program"
        self.label = f"{base}@mesh{self.n_shards}"
        self.gather_label = base.replace("/morsel:", "/gather:", 1) \
            + f"@mesh{self.n_shards}"
        self.module_name = program_name(base, name_fingerprint)[:56]
        # (direct-address, sort-based) joins of the local program, fixed
        # by its trace; run() moves direct_joins / sorted_joins by it once
        # a dispatch, not once a replica
        self.join_paths = (0, 0)
        self._fn = None
        self._gather = None
        self._replicated: dict = {}     # scan key -> (src id, replicated)
        self._lock = threading.Lock()

    # -- trace body (runs inside shard_map, one replica's block) -------------
    def _trace_local(self, morsel, others: tuple):
        scans = dict(zip(self._other_keys, others))
        scans[self.morsel_key] = morsel
        rec = _Recorder("replay", self.decisions)
        ex = JaxExecutor(_no_load, recorder=rec, scan_tables=scans,
                         mesh=None, shard_local=True)
        out = ex.replay(self.plan)
        self.join_paths = ex.join_paths
        if rec.idx != len(rec.decisions):
            raise ReplayMismatch("decision schedule length drift (sharded)")
        if ex.fallback_nodes:
            raise ReplayMismatch(
                f"fallback under sharded trace: {ex.fallback_nodes}")
        # checks ride out PER REPLICA as (1,)-shaped rows of a sharded
        # vector: the host sees all n values and verifies shard-aware
        checks = [c.reshape(1) for c in rec.checks]
        return out, checks

    @property
    def _other_keys(self) -> tuple:
        return tuple(k for k in self.scan_keys if k != self.morsel_key)

    def _build(self) -> None:
        axis = self.mesh.axis_names[0]
        local = shard_map(self._trace_local, mesh=self.mesh,
                          in_specs=(P(axis), P()),
                          out_specs=(P(axis), P(axis)), check_vma=False)
        self._fn = jax.jit(_named(local, self.module_name + "_local"))
        self._gather = jax.jit(_named(gather_partials(self.mesh),
                                      self.module_name + "_gather"))

    def _replicate(self, key: str, dt):
        """Commit a dimension-scan table replicated over the mesh once; the
        session's stream executor uploads it single-device and every morsel
        of every group reuses this broadcast copy."""
        cached = self._replicated.get(key)
        if cached is not None and cached[0] == id(dt):
            return cached[1]
        rep = jax.device_put(dt, NamedSharding(self.mesh, P()))
        self._replicated[key] = (id(dt), rep)
        return rep

    def _verify(self, checks_host: list) -> None:
        for (kind, planned), arr in zip(self.decisions, checks_host):
            a = np.asarray(arr)
            if kind == "cap":
                amax = int(a.max()) if a.size else 0
                if amax > bucket(max(int(planned), 1)):
                    raise ReplayMismatch(
                        f"sharded capacity overflow: {amax} > planned "
                        f"{planned}")
            else:
                vals = set(int(v) for v in a.tolist())
                if vals != {int(planned)}:
                    raise ReplayMismatch(
                        f"sharded exact decision drift: {sorted(vals)} != "
                        f"{planned}")

    def run(self, scans: dict, stats: Optional[dict] = None):
        """Dispatch the local program + the partial gather for one morsel;
        returns the host partial DTable (or tuple, fused groups) whose rows
        are the concatenation of every replica's partial block. `stats`
        accumulates collective_bytes / collective_ms (gather dispatch to
        result on the host) / local device_ms."""
        from ...resilience import FAULTS

        with self._lock:
            first = self._fn is None
            if first:
                FAULTS.fire("jax.compile")
                self._build()
        if first:
            _metrics.COMPILES.inc(2)   # local + gather programs
        FAULTS.fire("jax.execute")
        with TRACER.span("exec", cat="device", label=self.label,
                         first=first, shards=self.n_shards):
            t0 = time.perf_counter()
            with TRACER.span("exec.args", cat="device"):
                morsel = scans[self.morsel_key]
                others = tuple(self._replicate(k, scans[k])
                               for k in self._other_keys)
            with jax.profiler.TraceAnnotation(self.label):
                with TRACER.span("exec.wait", cat="device"):
                    out, checks = self._fn(morsel, others)
                    count_join_paths(self.join_paths)
                    if TRACER.enabled:
                        jax.block_until_ready((out, checks))
                with TRACER.span("exec.fetch", cat="device"):
                    checks_host = jax.device_get(checks)
                    t1 = time.perf_counter()
                    self._verify(checks_host)
                count_fetched(checks_host)
        if stats is not None and "decision_rows" in stats:
            # index-aligned per-decision actuals, the max over replicas
            # (what _verify held against each cap), handed out only to a
            # caller that pre-seeded the key, as CompiledQuery.run does
            stats["decision_rows"] = [
                int(np.asarray(a).max()) if np.size(a) else 0
                for a in checks_host]
        # ONE collective: all_gather of the sharded partial blocks. Bytes
        # model: ring all-gather ingress per device — each replica receives
        # the other n-1 replicas' blocks, (n-1)/n of the gathered total.
        sharded_bytes = sum(
            int(leaf.size) * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(out)
            if hasattr(leaf, "size"))
        coll_bytes = sharded_bytes * (self.n_shards - 1) // self.n_shards
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.gather_label):
            with TRACER.span("collective", cat="device",
                             label=self.gather_label, bytes=coll_bytes):
                merged = self._gather(out)
                if TRACER.enabled:
                    jax.block_until_ready(merged)
            # the gathered partials' copy to the host is a fetch like the
            # one-chip path's, not part of the collective
            with TRACER.span("exec.fetch", cat="device"):
                out_host = jax.device_get(merged)
        t3 = time.perf_counter()
        count_fetched(out_host)
        if stats is not None:
            stats["collective_bytes"] = \
                stats.get("collective_bytes", 0) + coll_bytes
            stats["collective_ms"] = round(
                stats.get("collective_ms", 0.0) + (t3 - t2) * 1000, 3)
            stats["device_ms"] = round(
                stats.get("device_ms", 0.0) + (t1 - t0) * 1000, 3)
        return out_host
