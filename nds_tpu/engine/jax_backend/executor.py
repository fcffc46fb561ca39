"""Device plan executor: walks a bound plan over DTables (JAX arrays).

Two execution modes (the TPU answer to the reference's accelerated plans,
reference nds/nds_power.py:124-134 + RAPIDS plugin):

- **Eager record**: each node executes as XLA compute over padded buffers
  through jitted kernels; row counts are host-synced only at shape-decision
  points (post filter/join/aggregate capacity planning), and every such
  decision is RECORDED into a capacity schedule.
- **Compiled replay**: on the next execution of the same query (unchanged
  table registrations), the entire plan is traced into ONE `jax.jit`
  program. Capacities come from the recorded schedule (static), row-alive
  masks from traced counts, and the program returns one check scalar per
  decision so the runner can verify the schedule still fits (mismatch =>
  schedule invalidated, eager re-record). Scan tables enter as jit
  arguments, so device-resident tables are shared across the whole query
  stream with zero per-query H2D transfer.

Any node the device backend does not cover falls back to the numpy oracle
backend for that node only (eager mode; such plans are never compiled).
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...obs import metrics as _metrics
from ...obs.trace import TRACER
from ..column import Table, dec_scale, is_dec
from ..executor import Executor as HostExecutor
from ..plan import (
    AggregateNode, AggSpec, BExpr, DistinctNode, FilterNode, JoinNode,
    LimitNode, MaterializedNode, PlanNode, ProjectNode, ScanNode, SetOpNode,
    SortNode, VirtualScanNode, WindowFunc, WindowNode, deparameterize_plan,
    iter_plan_nodes, parameterize_plan, replace_plan_nodes,
)
from . import jexprs, kernels
from .device import (DCol, DTable, PackedTable, bucket, decode_col,
                     device_bytes, encode_against, free_dtable, phys_dtype,
                     rank_key, string_rank_lut, to_device, to_host,
                     unpack_table, widen_col)

_I32 = jnp.int32

# XLA's compile and persistent-cache events count into METRICS from the
# first program on, eager host kernels of the record pass included
_metrics.install_xla_counters()


class NotJittable(Exception):
    """Raised at trace time when a plan needs host-side data-dependent work."""


class ReplayMismatch(Exception):
    """A compiled plan's capacity schedule no longer fits the data."""


class ArgSpecMismatch(ValueError):
    """Concrete arguments do not fit a compiled program's input contract.

    Raised with a PER-ARGUMENT expected-vs-got dtype/shape report (scan
    keys and parameter slots named) instead of the bare structural mismatch
    the JAX call site would produce — argument drift is the hardest
    compiled-replay failure to localize otherwise."""


#: the widest span of build keys the direct-address join addresses: two i32
#: tables (the uniqueness histogram, the lookup table) of at most 64 MB each
_DIRECT_SPAN_MAX = 1 << 24

_NOJIT_ERRORS = (NotJittable, NotImplementedError,
                 jax.errors.TracerArrayConversionError,
                 jax.errors.ConcretizationTypeError)


def program_name(label: str, fingerprint: Optional[str] = None) -> str:
    """The name a plan program is jitted under: ``nds_<query>_<unit>``
    (``[A-Za-z0-9_]``, at most 64 characters). JAX calls the HLO module
    ``jit_<name>``, so the device trace's ``XLA Modules`` line, the compile
    cache's file names and every instruction's ``op_name``
    (``jit(nds_query9_root)/.../AggregateNode#3/agg_apply/...``) carry it.

    ``label`` is the program's ``<query>/<unit>`` label. Where the query
    part is no name a caller gave but ``Session._auto_label``'s hash of
    the SQL text, pass the parameterized plan's ``fingerprint`` and it
    takes the query's place: one hoisted program then has one name for
    every literal. The module name is part of JAX's compile-cache key, so
    nothing that differs between two processes running the same statement
    may enter it — no ``id()``, no counter, no stream number, no
    parameter value."""
    query, _, unit = label.partition("/")
    unit = unit or "root"           # an unsegmented statement is its root
    if fingerprint:
        query = "plan" + fingerprint[:12]
    name = "nds_" + re.sub(r"[^A-Za-z0-9]+", "_",
                           f"{query}_{unit}").strip("_")
    if len(name) > 64:
        name = name[:55] + "_" + hashlib.sha1(name.encode()).hexdigest()[:8]
    return name


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name`` for ``jax.jit``, which takes the module's name
    from the function's ``__name__`` (a bound method cannot be renamed)."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


class _Recorder:
    """Capacity-decision schedule: recorded eagerly, consumed under trace."""
    __slots__ = ("mode", "decisions", "idx", "checks", "nodes")

    def __init__(self, mode: str, decisions: Optional[list] = None):
        self.mode = mode                    # "record" | "replay"
        self.decisions = decisions if decisions is not None else []
        self.idx = 0
        self.checks: list[jax.Array] = []   # traced actuals (replay only)
        # record mode: the plan node whose execution made each decision
        # (index-aligned with `decisions`; None = a decision with no row
        # semantics). Replay checks are index-aligned too, so per-node
        # ACTUAL row counts ride out of every compiled run for free
        # (ExecStats.node_stats — the schedule already fetches the checks
        # host-side for verification).
        self.nodes: list = []


# Cross-stream/-session compiled-program registry (VERDICT r4 #4): stream
# variants of one template parameterize to THE SAME plan (parameterize_plan),
# so the first stream's recorded schedule + compiled program can serve every
# later stream with different parameter VALUES — no re-record, no re-trace,
# no compile. Keyed by a structural plan fingerprint; capacity drift between
# streams is caught by _verify_schedule (caps are <=-checked) and handled by
# re-recording with per-slot max-merged caps, so the program converges to a
# shape serving all streams. Exact-decision drift marks the entry volatile
# (per-stream programs, the pre-registry behavior). The reference's analog
# is Spark reusing planned queries across streams (nds/nds_power.py:124-134).
_SHARED_PROGRAMS: dict = {}
_SHARED_LOCK = threading.Lock()

#: fault/ReplayMismatch strikes per shared fingerprint (quarantine below)
_PROGRAM_STRIKES: dict = {}
#: strikes before a shared entry is quarantined (evicted, re-recorded on
#: next use). One strike is normal life — a single capacity drift or a
#: transient device fault repairs through the serial fallback; the same
#: entry failing repeatedly means the PROGRAM is poisoned (bad schedule,
#: corrupted executable) and every adopter inherits the failure.
QUARANTINE_STRIKES = 3


def clear_shared_programs() -> None:
    """Test hook: drop all cross-session shared programs."""
    with _SHARED_LOCK:
        _SHARED_PROGRAMS.clear()
        _PROGRAM_STRIKES.clear()


def strike_shared_program(fp: Optional[str], reason: str = "") -> bool:
    """Record one fault/ReplayMismatch strike against a shared program.

    At QUARANTINE_STRIKES the entry is QUARANTINED: evicted from
    _SHARED_PROGRAMS (and its strike history cleared) so the next use
    re-records and re-publishes a fresh schedule/program instead of every
    adopter replaying the poisoned one. Returns True when this strike
    evicted the entry. Thread-safe; counted in ``quarantined_programs``
    and recorded as a flight ``quarantine`` event.
    """
    if fp is None:
        return False
    with _SHARED_LOCK:
        n = _PROGRAM_STRIKES.get(fp, 0) + 1
        _PROGRAM_STRIKES[fp] = n
        if n < QUARANTINE_STRIKES:
            return False
        _PROGRAM_STRIKES.pop(fp, None)
        if _SHARED_PROGRAMS.pop(fp, None) is None:
            return False
    from ...obs.flight import FLIGHT
    from ...obs.metrics import QUARANTINED_PROGRAMS
    QUARANTINED_PROGRAMS.inc()
    FLIGHT.record("quarantine", fp=fp[:12], strikes=n,
                  reason=reason or "repeated failures")
    return True


def shared_programs_snapshot() -> list:
    """``system.programs`` rows: one per shared compiled-program cache
    entry, cut atomically under the registry lock. ``hits`` counts
    cross-stream adoptions, ``compiles`` the programs published under
    the fingerprint, ``strikes`` the live quarantine strikes (an entry
    at QUARANTINE_STRIKES is already evicted, so live strikes are
    always below the threshold)."""
    with _SHARED_LOCK:
        return [{"fingerprint": fp,
                 "hits": sh.get("adoptions", 0),
                 "compiles": sh.get("compiles", 0),
                 "strikes": _PROGRAM_STRIKES.get(fp, 0),
                 "volatile": bool(sh.get("volatile")),
                 "nojit": bool(sh.get("nojit")),
                 "decisions": len(sh.get("decisions", ()))}
                for fp, sh in sorted(_SHARED_PROGRAMS.items())]


def absolve_shared_program(fp: Optional[str]) -> None:
    """A successful run through the shared entry: clear its strikes
    (strikes mark a PERSISTENTLY failing program, not one that hiccuped
    once between healthy runs)."""
    if fp is None:
        return
    with _SHARED_LOCK:
        _PROGRAM_STRIKES.pop(fp, None)


def shared_fingerprint(pplan, shard_min_rows: int) -> str:
    """Registry key of a parameterized unit plan in _SHARED_PROGRAMS.

    Module-level so the query service's PLANNER stage (which must not touch
    the device-lane executor from its worker threads) computes the same key
    the executor publishes under: plan structure + the compile-relevant
    engine configuration (x64 tier, shard threshold)."""
    x64 = jax.config.read("jax_enable_x64")
    body = _plan_fingerprint(pplan)
    return hashlib.sha1(
        f"{body}|x64={x64}|smr={shard_min_rows}".encode()).hexdigest()


def _node_rows(decisions: list, node_labels: tuple, actuals: list) -> dict:
    """{TypeName#k: actual rows} from index-aligned (decision, label,
    actual) triples — the per-node actual row counts the schedule already
    computes (capacity syncs at record, fetched checks at replay). Labels
    match the plan verifier's node identities, so profiles, findings, and
    ``ExecStats.node_stats`` all name the same node; a node with several
    decisions keeps its largest (the output-row sync dominates probes)."""
    rows: dict = {}
    for (kind, _planned), lbl, actual in zip(decisions, node_labels,
                                             actuals):
        if lbl is None or kind not in ("cap", "exact"):
            continue
        a = int(actual)
        if lbl not in rows or a > rows[lbl]:
            rows[lbl] = a
    return rows


def count_fetched(fetched) -> None:
    """Add what one jax.device_get returned to bytes_fetched."""
    _metrics.BYTES_FETCHED.inc(device_bytes(fetched))


_JOIN_PATH_COUNTERS = (_metrics.DIRECT_JOINS, _metrics.SORTED_JOINS,
                       _metrics.SCAN_ROWS, _metrics.DIRECT_PROBE_ROWS,
                       _metrics.SORTED_PROBE_ROWS,
                       _metrics.EXPANDED_JOIN_ROWS)


def count_join_paths(join_paths: tuple) -> None:
    """Add one dispatched program's JaxExecutor.join_paths to direct_joins /
    sorted_joins and to the four row counters beside them; a program that
    has not been traced yet holds a shorter tuple, which reads as zeros."""
    for counter, n in zip(_JOIN_PATH_COUNTERS, join_paths):
        counter.inc(n)


def _verify_schedule(decisions: list, checks_host: list) -> None:
    for (kind, planned), actual in zip(decisions, checks_host):
        a = int(actual)
        if kind == "cap":
            if a > bucket(max(int(planned), 1)):
                raise ReplayMismatch(f"capacity overflow: {a} > planned "
                                     f"{planned}")
        else:  # exact
            if a != int(planned):
                raise ReplayMismatch(f"exact decision drift: {a} != {planned}")


class CompiledQuery:
    """One whole-plan XLA program built from a recorded capacity schedule.

    Scan tables enter as a TUPLE in first-touch order and hoisted stream
    literals as a parameter vector: the traced program is therefore
    byte-identical across streams/seeds of one template (same structure,
    same capacities), and the persistent XLA cache serves every stream
    after the first compile.

    `plan` may be a LIST of plans (shared-scan fused morsel groups,
    streaming.fuse_group): the plans trace in order under ONE decision
    schedule — recorded by JaxExecutor.record_plans — into one multi-output
    program, and run() returns a tuple of DTables. The fixed per-dispatch
    cost is then paid once per morsel instead of once per branch."""

    def __init__(self, plan, decisions: list, scan_keys: tuple,
                 mesh=None, param_dtypes: tuple = (),
                 shard_min_rows: int = 1 << 18, label: str = "",
                 decision_nodes: Optional[tuple] = None,
                 name_fingerprint: Optional[str] = None):
        self.plan = plan
        self.decisions = decisions
        self.scan_keys = scan_keys
        # per-decision TypeName#k attribution (record-time; index-aligned
        # with decisions/checks): lets every replay report the per-node
        # actual row counts its schedule checks already fetched
        self.decision_nodes = decision_nodes
        self.mesh = mesh
        self.param_dtypes = param_dtypes
        self.shard_min_rows = shard_min_rows
        # "<query>/<unit>": the label of this program's spans and host
        # annotations, and (through program_name) of its HLO module on the
        # device trace. name_fingerprint: see program_name
        self.label = label or "program"
        self.module_name = program_name(self.label, name_fingerprint)
        # FilterNodes of this program that carry their mask instead of
        # compacting (JaxExecutor._maybe_compact): fixed by the trace, 0
        # under a mesh; run() moves mask_carried_filters by it
        self.mask_carried = 0
        # (direct-address, sort-based) joins of this program and the rows
        # its scans, probes and expansions hold (JaxExecutor.join_paths):
        # which path each JoinNode took is a recorded decision and every
        # capacity a shape, so fixed by the trace
        self.join_paths = (0, 0)
        # (window nodes, rollup grouping sets, set operations, outer joins,
        # joins whose build side is a star's join tree) this program holds;
        # count_dispatch() moves their counters by it
        self.plan_shapes = _plan_shapes(plan)
        self._fn = None
        self._aot = None     # AOT executable from precompile()
        self._aot_specs = None  # flat (shape, dtype) list the AOT was lowered for
        self._aot_arg_specs = None  # per-argument [(label, specs)] for reports
        # _SHARED_PROGRAMS hands one CompiledQuery to every stream of a
        # template: concurrent multi-stream runs must not race the lazy
        # _fn/_aot initialization (ADVICE r5)
        self._lock = threading.Lock()

    def _trace(self, scan_tuple: tuple, params: tuple):
        scans = dict(zip(self.scan_keys, scan_tuple))
        rec = _Recorder("replay", self.decisions)
        # the mesh AND size thresholds MUST match the recording executor's:
        # static branches (compaction skip, shard-local aggregation, the
        # shuffle-join gate) key on them, and a mismatched replay would
        # consume a differently-shaped schedule
        ex = JaxExecutor(_no_load, recorder=rec, scan_tables=scans,
                         mesh=self.mesh, params=params,
                         shard_min_rows=self.shard_min_rows)
        out = ex.replay(self.plan)
        self.mask_carried = ex.mask_carried
        self.join_paths = ex.join_paths
        if rec.idx != len(rec.decisions):
            raise NotJittable("decision schedule length drift")
        if ex.fallback_nodes:
            raise NotJittable(f"fallback under trace: {ex.fallback_nodes}")
        return out, rec.checks

    def count_dispatch(self) -> None:
        """One dispatch of this program: what its plan holds, counted."""
        _metrics.MASK_CARRIED_FILTERS.inc(self.mask_carried)
        count_join_paths(self.join_paths)
        for counter, n in zip(_PLAN_SHAPE_COUNTERS, self.plan_shapes):
            counter.inc(n)

    def _args(self, scans: dict, values: tuple) -> tuple:
        missing = [k for k in self.scan_keys if k not in scans]
        if missing:
            raise ArgSpecMismatch(
                f"missing scan argument(s) {missing} "
                f"(program takes {len(self.scan_keys)} scan(s): "
                f"{list(self.scan_keys)})")
        if len(values) != len(self.param_dtypes):
            # zip would silently truncate: a short parameter vector would
            # execute with the wrong literals, not fail
            raise ArgSpecMismatch(
                f"parameter vector length mismatch: program expects "
                f"{len(self.param_dtypes)} hoisted parameter(s) with "
                f"dtypes {list(self.param_dtypes)}, got "
                f"{len(values)} value(s)")
        scan_tuple = tuple(scans[k] for k in self.scan_keys)
        params = tuple(jnp.asarray(v, dtype=phys_dtype(d))
                       for v, d in zip(values, self.param_dtypes))
        return scan_tuple, params

    def precompile(self, scan_specs: tuple, stats: Optional[dict] = None):
        """Trace + compile ahead of execution from abstract arg specs
        (jax.ShapeDtypeStruct trees mirroring the scan tables) WITHOUT
        uploading data. Raises the same _NOJIT_ERRORS a traced run would.
        The resulting AOT executable serves run() directly; XLA compiles
        outside the GIL, so callers fan precompile() calls out over a
        thread pool (one compile per segment/query at once instead of
        serial-at-first-execution)."""
        import time as _time

        from ...resilience import FAULTS
        FAULTS.fire("jax.compile")
        with self._lock:
            if self._fn is None:
                self._fn = jax.jit(_named(self._trace, self.module_name))
            fn = self._fn
        params = tuple(jax.ShapeDtypeStruct((), phys_dtype(d))
                       for d in self.param_dtypes)
        t0 = _time.perf_counter()
        with TRACER.span("compile", cat="compile", label=self.label):
            aot = fn.lower(scan_specs, params).compile()
        _metrics.COMPILES.inc()
        with self._lock:
            self._aot = aot
            self._aot_specs = self._flat_specs((scan_specs, params))
            self._aot_arg_specs = self._arg_spec_table(scan_specs, params)
        if stats is not None:
            stats["precompile_s"] = round(_time.perf_counter() - t0, 3)

    @staticmethod
    def _flat_specs(tree) -> Optional[list]:
        """Flat (shape, dtype) list of a pytree of arrays/specs; None when a
        leaf carries neither (spec checking is then unavailable)."""
        leaves = jax.tree_util.tree_leaves(tree)
        out = []
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                return None
            out.append((tuple(shape), np.dtype(dtype)))
        return out

    def _specs_match(self, args) -> bool:
        """Do concrete args structurally fit the AOT executable's input
        specs? Shape/dtype only — shardings/placement are re-checked by the
        runtime itself (the narrow except in run())."""
        if self._aot_specs is None:
            return False
        got = self._flat_specs(args)
        return got is not None and got == self._aot_specs

    def _arg_spec_table(self, scan_tuple, params) -> list:
        """[(argument label, flat specs)] with one entry per program
        argument: scan tables by their cache key, parameter slots by index
        and engine dtype — the unit of the expected-vs-got report."""
        table = []
        for k, s in zip(self.scan_keys, scan_tuple):
            table.append((f"scan {k!r}", self._flat_specs(s)))
        for i, (p, d) in enumerate(zip(params, self.param_dtypes)):
            table.append((f"param {i} ({d})", self._flat_specs((p,))))
        return table

    @staticmethod
    def _fmt_spec(spec) -> str:
        shape, dtype = spec
        return f"{dtype}[{','.join(map(str, shape))}]"

    def spec_mismatch_report(self, scans: dict, values: tuple = ()
                             ) -> Optional[str]:
        """Per-argument expected-vs-got dtype/shape report against the
        precompiled input specs; None when everything fits (or no AOT
        specs exist to validate against)."""
        if self._aot_arg_specs is None:
            return None
        scan_tuple, params = self._args(scans, values)
        got_table = self._arg_spec_table(scan_tuple, params)
        lines: list[str] = []
        for (label, exp), (_, got) in zip(self._aot_arg_specs, got_table):
            if exp == got:
                continue
            if exp is None or got is None:
                lines.append(f"{label}: argument is not inspectable")
                continue
            if len(exp) != len(got):
                lines.append(f"{label}: expected {len(exp)} array(s) "
                             f"(e.g. columns/validity), got {len(got)}")
                continue
            for j, (e, g) in enumerate(zip(exp, got)):
                if e != g:
                    lines.append(
                        f"{label} leaf {j}: expected "
                        f"{self._fmt_spec(e)}, got {self._fmt_spec(g)}")
        return "\n".join(lines) or None

    def validate_args(self, scans: dict, values: tuple = ()) -> None:
        """Raise ArgSpecMismatch naming every drifted argument (expected vs
        got dtype/shape) when the concrete args do not fit the compiled
        program; silently returns when they fit or nothing is compiled."""
        report = self.spec_mismatch_report(scans, values)
        if report:
            raise ArgSpecMismatch(
                "compiled program argument mismatch:\n" + report)

    def run(self, scans: dict, values: tuple = (),
            stats: Optional[dict] = None,
            keep_device: bool = False) -> DTable:
        import time as _time

        from ...resilience import FAULTS
        with self._lock:
            first = self._fn is None
            if first:
                FAULTS.fire("jax.compile")
                self._fn = jax.jit(_named(self._trace, self.module_name))
            fn, aot = self._fn, self._aot
        if first:
            _metrics.COMPILES.inc()   # jit path compiles inside the call
        FAULTS.fire("jax.execute")
        # the compiled-program dispatch is the unit the host's side of
        # device time is measured at (the Flare lesson): exec = exec.args
        # (build and check the arguments) + exec.wait (the call, and with
        # the tracer on the wait until the outputs are ready) + exec.fetch
        # (device-to-host copy, schedule check). The device's side is the
        # module's row on the profiler's XLA Modules line
        with TRACER.span("exec", cat="device", label=self.label,
                         first=first):
            t1 = _time.perf_counter()
            with TRACER.span("exec.args", cat="device"):
                args = self._args(scans, values)
                if aot is not None and not self._specs_match(args):
                    # shape/dtype drift against the precompiled specs: take
                    # the jit path explicitly (the persistent compile cache
                    # still serves the binary when the lowering matches)
                    # instead of letting the AOT call fail and masking the
                    # error class. The per-argument expected-vs-got report
                    # lands in stats so the drift is attributable to a
                    # specific scan/param, not a bare mismatch.
                    if stats is not None:
                        report = self.spec_mismatch_report(scans, values)
                        if report:
                            stats["spec_mismatch"] = report
                    with self._lock:
                        if self._aot is aot:
                            self._aot = None
                    aot = None
            with jax.profiler.TraceAnnotation(self.label):
                with TRACER.span("exec.wait", cat="device"):
                    if aot is not None:
                        try:
                            out, checks = aot(*args)
                        except (TypeError, ValueError) as aot_err:
                            # drift the shape check cannot see (committed-
                            # device / sharding mismatch). Retry via jit
                            # once; a jit failure of the SAME class is a
                            # genuine runtime error — re-raise it with the
                            # AOT error as explicit context instead of
                            # swallowing the original.
                            with self._lock:
                                if self._aot is aot:
                                    self._aot = None
                            try:
                                out, checks = fn(*args)
                            except type(aot_err):
                                raise aot_err
                    else:
                        out, checks = fn(*args)
                    self.count_dispatch()
                    if TRACER.enabled:
                        jax.block_until_ready((out, checks))
                with TRACER.span("exec.fetch", cat="device"):
                    # ONE device_get for result + checks: every transfer
                    # synchronises with the device, so piecemeal np.asarray
                    # would dominate. keep_device (segment outputs feeding
                    # downstream programs): only the check scalars come
                    # back.
                    if keep_device:
                        checks_host = jax.device_get(checks)
                        out_host = out
                    else:
                        out_host, checks_host = jax.device_get((out, checks))
                    t2 = _time.perf_counter()
                    _verify_schedule(self.decisions, checks_host)
                count_fetched(checks_host if keep_device
                              else (out_host, checks_host))
        if stats is not None:
            checks_int = [int(c) for c in checks_host]
            if "decision_rows" in stats:
                # raw index-aligned per-decision actuals, exported ONLY
                # when the caller pre-seeded the key (the streaming loop,
                # which sizes a second sighting from them) — an
                # unconditional write would leak the list into every
                # in-core ExecStats.extra
                stats["decision_rows"] = checks_int
            if self.decision_nodes:
                rows = _node_rows(self.decisions, self.decision_nodes,
                                  checks_int)
                if rows:
                    stats["node_rows"] = rows
        device_ms = round((t2 - t1) * 1000, 3)
        if stats is not None:
            stats.update(mode="compile+run" if first else "compiled",
                         device_ms=device_ms)
        return out_host


class BatchedQuery:
    """One compiled program replayed over a STACKED batch of parameter
    vectors — the query service's compatible-plan batching unit.

    K admitted queries that parameterize to the same plan fingerprint
    (same structure, same recorded capacities, same scan tables, different
    hoisted literal VALUES) are served by a single dispatch: each parameter
    slot stacks into a (cap,)-vector and ``lax.map`` replays the SAME
    traced program per row, so row i's computation graph — and therefore
    its result — is exactly the single-query program's. The batch capacity
    rides the same ladder as row capacities (device.bucket), bounding the
    compile count to one batched program per (fingerprint, batch-capacity);
    short batches pad by duplicating the last real row (identical checks,
    discarded outputs).

    Schedule checks come back as (cap,)-vectors and verify batch-aware,
    exactly like sharded-morsel replays (shard_exec): cap decisions check
    max-over-batch <= bucket, exact decisions check all-equal — any row
    drifting raises ReplayMismatch and the caller serves the batch
    serially through the normal record/replay path instead."""

    def __init__(self, cq: CompiledQuery, cap: int):
        self.cq = cq
        self.cap = cap
        self.label = f"{cq.label}@batch{cap}"
        self.module_name = f"{cq.module_name[:56]}_batch{cap}"
        self._fn = None
        self._lock = threading.Lock()

    def _trace(self, scan_tuple: tuple, stacked: tuple):
        def one(params):
            out, checks = self.cq._trace(scan_tuple, tuple(params))
            return out, tuple(checks)
        return lax.map(one, stacked)

    def _verify(self, checks_host) -> None:
        for (kind, planned), actual in zip(self.cq.decisions, checks_host):
            a = np.asarray(actual)
            if kind == "cap":
                if int(a.max()) > bucket(max(int(planned), 1)):
                    raise ReplayMismatch(
                        f"batched capacity overflow: {int(a.max())} > "
                        f"planned {planned}")
            elif not bool((a == int(planned)).all()):
                raise ReplayMismatch(
                    f"batched exact decision drift: {a.tolist()} != "
                    f"{planned}")

    def run(self, scans: dict, rows: list,
            stats: Optional[dict] = None) -> list:
        """Run ``rows`` (parameter-value tuples, len <= cap) in ONE
        dispatch; returns one HOST-side DTable per row (numpy leaves —
        device_get happens once for the whole stacked output)."""
        import time as _time

        from ...resilience import FAULTS
        with self._lock:
            first = self._fn is None
            if first:
                FAULTS.fire("jax.compile")
                self._fn = jax.jit(_named(self._trace, self.module_name))
            fn = self._fn
        if first:
            _metrics.COMPILES.inc()
        FAULTS.fire("jax.execute")
        with TRACER.span("exec", cat="device", label=self.label,
                         first=first, batch=len(rows)):
            t1 = _time.perf_counter()
            with TRACER.span("exec.args", cat="device"):
                dts = self.cq.param_dtypes
                full = list(rows) + [rows[-1]] * (self.cap - len(rows))
                stacked = tuple(
                    jnp.asarray([r[j] for r in full], dtype=phys_dtype(d))
                    for j, d in enumerate(dts))
                scan_tuple = tuple(scans[k] for k in self.cq.scan_keys)
            with jax.profiler.TraceAnnotation(self.label):
                with TRACER.span("exec.wait", cat="device"):
                    out, checks = fn(scan_tuple, stacked)
                    self.cq.count_dispatch()
                    if TRACER.enabled:
                        jax.block_until_ready((out, checks))
                with TRACER.span("exec.fetch", cat="device"):
                    out_host, checks_host = jax.device_get((out, checks))
                    t2 = _time.perf_counter()
                    self._verify(checks_host)
                count_fetched((out_host, checks_host))
        device_ms = round((t2 - t1) * 1000, 3)
        if stats is not None:
            stats.update(mode="batched", device_ms=device_ms,
                         batch=len(rows))
        return [jax.tree_util.tree_map(lambda x: x[i], out_host)
                for i in range(len(rows))]


def _no_load(name: str) -> Table:
    raise NotJittable(f"table load of {name!r} under trace")


class JaxExecutor:
    """Executes bound plans on the JAX backend with per-node host fallback.

    One instance lives on the Session (scan cache + compiled plans persist
    across the query stream); replay instances are created per trace.
    """

    def __init__(self, load_table: Callable[[str], Table],
                 trace: Optional[Callable[[str, float, int], None]] = None,
                 recorder: Optional[_Recorder] = None,
                 scan_tables: Optional[dict] = None,
                 jit_plans: bool = True,
                 mesh=None,
                 shard_min_rows: int = 1 << 18,
                 segment_plan_nodes: int = 18,
                 segment_min_cte_nodes: int = 8,
                 segment_cache_entries: int = 16,
                 scan_budget_bytes: int = 10 << 30,
                 params: Optional[tuple] = None,
                 shard_local: bool = False):
        self._load_table = load_table
        # the plan node currently executing (execute() maintains it):
        # capacity decisions made while it runs attribute to it, so the
        # recorded schedule doubles as a per-node actual-row-count source
        self._cur_node = None
        # per-decision node list of the last record_plan/record_plans pass
        self._last_record_nodes: Optional[list] = None
        # shard-local mode (sharded morsel execution, shard_exec): this
        # executor's trace runs INSIDE a shard_map body, one replica's rows
        # at a time. Schedule-shaping gates behave like the mesh path (no
        # data-dependent tier probes, no compaction — per-shard data would
        # drift the recorded exact decisions), but execution strategies stay
        # single-device (no in-plan collectives: the shard_map boundary IS
        # the collective).
        self._shard_local = bool(shard_local)
        # hoisted literal values for the in-flight execution: python scalars
        # under eager record, traced 0-d arrays under compiled replay
        self._params = params
        self._memo: dict[int, DTable] = {}
        # ids of the in-flight plan's FilterNodes that carry their mask
        # instead of compacting (_mask_carrying_filters; _begin sets it per
        # plan), and how many of them this executor has run
        self._mask_carry: frozenset = frozenset()
        self.mask_carried = 0
        # JoinNodes this executor ran through _fast_join, and through the
        # sort-based path (dense_rank + build_side + probe_counts_by_gid)
        self.direct_joins = 0
        self.sorted_joins = 0
        # rows by capacity (shapes, so Python integers under a trace too):
        # of every table scan, of the probe side of every direct and of
        # every sort-based join, and of every expansion's output
        self.scan_rows = 0
        self.direct_probe_rows = 0
        self.sorted_probe_rows = 0
        self.expanded_join_rows = 0
        self._scan_cache: dict[str, DTable] = scan_tables if scan_tables \
            is not None else {}           # accelerator-resident tables
        self._trace = trace
        self._rec = recorder
        self._replay = recorder is not None and recorder.mode == "replay"
        self._jit_plans = jit_plans
        self._plans: dict = {}           # query key -> plan/schedule entry
        self._touched_scans: dict[str, None] = {}   # ordered set (first touch)
        self._scan_meta: dict[str, tuple] = {}   # key -> (table, cols, names)
        self.fallback_nodes: list[str] = []   # observability: who fell back
        # label of the in-flight query (Session.sql sets it); compile units
        # recorded during the run inherit "<label>/<unit>" program labels
        # for device-time attribution
        self.query_label: str = ""
        # the label is Session._auto_label's hash of the SQL text, not a
        # name the caller gave: programs are then named from the
        # parameterized plan's fingerprint (program_name)
        self.query_label_auto: bool = False
        # replay only: {id(node): "TypeName#k"} (verify.node_labels) of the
        # plan being traced, the named scopes execute() opens
        self._scope_labels: dict = {}
        # SPMD execution: with a mesh, fact-sized scans upload row-sharded
        # (NamedSharding over the first axis); GSPMD partitions the compiled
        # whole-plan program and inserts the collectives (the Spark-shuffle
        # role, SURVEY.md §2 parallelism table last row). Dimension-sized
        # tables replicate (broadcast-join layout).
        self._mesh = mesh
        self._shard_min_rows = shard_min_rows
        # CTE-boundary compile segmentation (VERDICT r2 #1): plans above the
        # node threshold split each large CTE into its own compile unit
        self._seg_plan_nodes = segment_plan_nodes
        self._seg_min_cte = segment_min_cte_nodes
        self._seg_cache_entries = segment_cache_entries
        self._segment_lru: list[str] = []
        self._pinned_segments: set[str] = set()
        # HBM accounting for the accelerator-resident cache: key -> bytes,
        # in LRU order (python dicts preserve insertion; re-touch moves to
        # the end). Evicting frees the arrays for XLA to reuse.
        self._scan_budget = scan_budget_bytes
        self._resident: dict[str, int] = {}
        # fingerprint whose shared program just ReplayMismatched here: the
        # post-mismatch re-record must not re-adopt it (see _adopt_shared)
        self._fp_block: Optional[str] = None
        # batched compiled programs (query-service compatible-plan
        # batching): (fingerprint, batch capacity) -> BatchedQuery
        self._batched: dict = {}
        # Eager (record / nojit) execution ALWAYS runs on the host CPU
        # backend when the default device is an accelerator: the record
        # pass only needs the capacity schedule + a correct result, and op
        # by op on the chip every kernel of every shape would pay its own
        # XLA:TPU compile. Compiled replay runs on the accelerator. One
        # behaviour however JAX_PLATFORMS was set: config.ensure_host_backend
        # (Session.__init__) keeps the CPU backend initialised beside the
        # accelerator, and its absence is an error, not an op-by-op record
        # on the chip.
        self._eager_device = None
        self._scan_cache_rec: dict[str, DTable] = self._scan_cache
        if not self._replay and jax.default_backend() != "cpu":
            try:
                self._eager_device = jax.devices("cpu")[0]
            except RuntimeError as e:
                raise RuntimeError(
                    "the record pass runs on the host CPU backend, which "
                    "this process did not initialise (jax_platforms="
                    f"{jax.config.jax_platforms!r}): list cpu after the "
                    "accelerator in JAX_PLATFORMS, or construct the Session "
                    "before anything else touches JAX") from e
            self._scan_cache_rec = {}
            if mesh is not None:
                # found on four v5e chips (PERF.md PR 21): the recorded
                # schedule of a GSPMD plan contains shard_map sites, and
                # the host pass cannot hand CPU arrays to a chip mesh
                raise NotImplementedError(
                    "mesh_shape (GSPMD whole-plan sharding) does not run "
                    f"on a {jax.default_backend()} mesh yet: the record "
                    "pass runs on the host CPU backend and its shard_map "
                    "sites cannot take host arrays over an accelerator "
                    "mesh (ROADMAP R7). Sharded morsels (mesh_shards) do "
                    "run on the chips")
        if mesh is not None and self._scan_cache_rec is self._scan_cache:
            # single-host CPU mesh (tests/dryrun): record single-device,
            # execute sharded — the caches hold different layouts
            self._scan_cache_rec = {}

    @property
    def join_paths(self) -> tuple:
        """(direct-address joins, sort-based joins, scanned rows, rows that
        probed directly, rows that probed through the sort, rows of expanded
        join outputs) run so far, rows by capacity: what a program traced
        through this executor moves direct_joins, sorted_joins, scan_rows,
        direct_probe_rows, sorted_probe_rows and expanded_join_rows by at
        each dispatch (count_join_paths)."""
        return (self.direct_joins, self.sorted_joins, self.scan_rows,
                self.direct_probe_rows, self.sorted_probe_rows,
                self.expanded_join_rows)

    def _exec_sharding(self, capacity: int):
        """Placement for an accelerator-resident scan of given capacity."""
        if self._mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        axis = self._mesh.axis_names[0]
        if capacity >= max(self._shard_min_rows,
                           self._mesh.size) and capacity % self._mesh.size == 0:
            return NamedSharding(self._mesh, P(axis))
        return NamedSharding(self._mesh, P())

    # -- public --------------------------------------------------------------
    def run_query(self, key, plan_factory: Callable[[], PlanNode]) -> DTable:
        """Session entry point: cached compiled execution when possible.

        key: hashable query identity (SQL text); None disables caching.

        Large multi-CTE plans are segmented at CTE boundaries into several
        compile units (see _segment_plan): each CTE materializes once as a
        device-resident table, shared across this query's parts AND across
        statements with an identical WITH clause (q14/q23 parts). Bounded
        XLA compile time replaces the reference's rely-on-Spark-planner
        property (nds/nds_power.py:124-134) that q4-class plans broke here.
        """
        self.fallback_nodes = []
        self.last_stats: dict = {}
        meta_key = ("segmeta", key) if key is not None else None
        meta = self._plans.get(meta_key) if meta_key is not None else None
        if meta is None:
            plan = plan_factory()
            units = self._segment_plan(plan)
            if meta_key is not None and self._jit_plans:
                self._plans[meta_key] = {"units": units}
        else:
            units = meta["units"]
        if len(units) == 1:
            return self._run_unit(key, units[0][1])
        seg_ms = 0.0
        segs_run = 0
        out = None
        # second sighting of a multi-unit query: every unit has a recorded
        # schedule but no program yet — compile them CONCURRENTLY before
        # executing (q22's 7 rollup segments compile in max() not sum())
        if key is not None and self._jit_plans:
            unit_keys = [((key, "root") if sk is None else (key, sk))
                         for sk, _ in units]
            if any(self._plans.get(uk, {}).get("decisions") is not None
                   and self._plans[uk].get("cq") is None
                   and not self._plans[uk].get("nojit")
                   for uk in unit_keys):
                self.precompile_parallel(keys=set(unit_keys))
        # pin this query's segments: LRU pressure from binding segment N
        # must never evict segment M still needed by a later unit
        self._pinned_segments = {sk for sk, _ in units if sk is not None}
        try:
            for seg_key, uplan in units:
                self.last_stats = {}     # per-unit stats; no cross-unit leaks
                if seg_key is None:
                    root_key = (key, "root") if key is not None else None
                    out = self._run_unit(root_key, uplan)
                    continue
                if seg_key in self._scan_cache or \
                        seg_key in self._scan_cache_rec:
                    self._touch_segment(seg_key)
                    continue
                unit_key = (key, seg_key) if key is not None else None
                seg_out = self._run_unit(unit_key, uplan, keep_device=True)
                self._bind_segment(seg_key, seg_out)
                segs_run += 1
                seg_ms += self.last_stats.get("device_ms", 0.0)
        finally:
            self._pinned_segments = set()
        root_stats = dict(self.last_stats)
        root_stats.update(segments=len(units) - 1, segments_run=segs_run,
                          seg_device_ms=round(seg_ms, 3))
        self.last_stats = root_stats
        return out

    # -- segmentation ---------------------------------------------------------
    def _segment_plan(self, plan: PlanNode) -> list:
        """Split a big plan into [(seg_key, unit_plan)...] + [(None, root)].

        Two cut classes, both yielding bounded XLA programs:
        - CTE boundaries (planner-fingerprinted, shared across statements);
        - rollup grouping-set boundaries (q67-class plans have no CTEs but
          compile one giant program per grouping set: the aggregate's child
          materializes once and each rollup level becomes its own unit).
        Units are in dependency order; a later unit sees earlier outputs as
        VirtualScanNodes resolved against the segment cache."""
        if not self._jit_plans or self._seg_plan_nodes <= 0:
            return [(None, plan)]
        out = []
        for seg_key, uplan in self._cte_units(plan):
            out.extend(self._rollup_units(seg_key, uplan))
        return out

    def _cte_units(self, plan: PlanNode) -> list:
        segs = getattr(plan, "cte_segments", None)
        if not segs:
            return [(None, plan)]
        nodes = list(iter_plan_nodes(plan))
        if len(nodes) < self._seg_plan_nodes:
            return [(None, plan)]
        reachable = {id(n) for n in nodes}
        mapping: dict[int, PlanNode] = {}
        units: list = []
        seen_keys: set[str] = set()
        for fp, node in segs:
            if id(node) not in reachable:
                continue
            if sum(1 for _ in iter_plan_nodes(node)) < self._seg_min_cte:
                continue
            seg_key = "seg:" + fp
            virt = VirtualScanNode(key=seg_key, label="cte",
                                   out_names=list(node.out_names),
                                   out_dtypes=list(node.out_dtypes))
            if seg_key not in seen_keys:
                seen_keys.add(seg_key)
                units.append((seg_key,
                              replace_plan_nodes(node, mapping)
                              if mapping else node))
            mapping[id(node)] = virt
        if not units:
            return [(None, plan)]
        units.append((None, replace_plan_nodes(plan, mapping)))
        return units

    def _rollup_units(self, seg_key, uplan: PlanNode) -> list:
        """Split big rollup aggregates in one compile unit into per-level
        units: [(child_seg, child), (level_seg, level_agg)..., (seg_key,
        rewritten)]. The rewrite unions per-level VirtualScans, which is
        exactly the concat the in-program rollup performs."""
        nodes = list(iter_plan_nodes(uplan))
        if len(nodes) < self._seg_plan_nodes:
            return [(seg_key, uplan)]
        units: list = []
        mapping: dict[int, PlanNode] = {}
        cands = [n for n in nodes
                 if isinstance(n, AggregateNode) and n.rollup
                 and n.rollup_levels is None and len(n.group_exprs) >= 2]
        # innermost first: a rollup nested in another rollup's child must be
        # rewritten before the outer child unit is cut, or the outer unit
        # would still compile the inner one as a giant in-program rollup
        cands.sort(key=lambda a: sum(1 for _ in iter_plan_nodes(a)))
        for orig in cands:
            child_nodes = list(iter_plan_nodes(orig.child))
            if len(child_nodes) < self._seg_min_cte or \
                    any(isinstance(m, MaterializedNode) for m in child_nodes):
                continue
            child = replace_plan_nodes(orig.child, mapping) if mapping \
                else orig.child
            agg = dataclasses.replace(orig, child=child) if child \
                is not orig.child else orig
            ckey = "seg:" + _plan_fingerprint(child)
            virt_child = VirtualScanNode(
                key=ckey, label="rollup-src",
                out_names=list(child.out_names),
                out_dtypes=list(child.out_dtypes))
            units.append((ckey, child))
            branches: list[PlanNode] = []
            for lvl in range(len(agg.group_exprs), -1, -1):
                lnode = dataclasses.replace(agg, child=virt_child,
                                            rollup_levels=[lvl])
                lkey = "seg:" + _plan_fingerprint(lnode)
                units.append((lkey, lnode))
                branches.append(VirtualScanNode(
                    key=lkey, label=f"rollup-lvl{lvl}",
                    out_names=list(agg.out_names),
                    out_dtypes=list(agg.out_dtypes)))
            chain = branches[0]
            for v in branches[1:]:
                chain = SetOpNode(op="union", all=True, left=chain, right=v,
                                  out_names=list(agg.out_names),
                                  out_dtypes=list(agg.out_dtypes))
            mapping[id(orig)] = chain     # keyed by the ORIGINAL node id
        if not mapping:
            return [(seg_key, uplan)]
        return units + [(seg_key, replace_plan_nodes(uplan, mapping))]

    def _bind_segment(self, seg_key: str, out: DTable) -> None:
        """Stash a segment output for downstream units; LRU-bounded."""
        if self.last_stats.get("mode") in ("compiled", "compile+run"):
            self._scan_cache[seg_key] = out
            self._account_resident(seg_key, out)
        else:          # record/eager output lives on the record-side device
            self._scan_cache_rec[seg_key] = out
        self._touch_segment(seg_key)

    def _touch_segment(self, seg_key: str) -> None:
        if seg_key in self._segment_lru:
            self._segment_lru.remove(seg_key)
        self._segment_lru.append(seg_key)
        pinned = getattr(self, "_pinned_segments", set())
        evictable = [k for k in self._segment_lru if k not in pinned]
        while len(self._segment_lru) > self._seg_cache_entries and evictable:
            old = evictable.pop(0)
            self._segment_lru.remove(old)
            # free eagerly: a dropped reference alone leaves reclaiming
            # HBM to gc timing
            free_dtable(self._scan_cache.pop(old, None))
            self._resident.pop(old, None)
            if self._scan_cache_rec is not self._scan_cache:
                self._scan_cache_rec.pop(old, None)

    def _unit_label(self, key) -> str:
        """Label of a compile unit: "<query>/<unit>" — what its spans, its
        host annotations and (through program_name) its HLO module are
        called (segments keep a short fingerprint so q14/q23-style shared
        CTEs stay distinguishable)."""
        base = self.query_label or "query"
        if isinstance(key, tuple) and len(key) == 2 and \
                isinstance(key[1], str):
            if key[1].startswith("seg:"):
                return f"{base}/{key[1][:12]}"
            if key[1] == "root":
                return f"{base}/root"
            if key[0] == "stream-incore":    # Session._incore_partial
                return f"{base}/incore:{key[1][:8]}"
        return base

    def name_fingerprint(self, plan) -> Optional[str]:
        """What names ``plan``'s program in the query label's place
        (program_name): its fingerprint where the label is the hash of the
        SQL text, None where the caller gave the label."""
        if not self.query_label_auto:
            return None
        return _plan_fingerprint(plan, mat_by_identity=False)

    def _new_cq(self, key, ent) -> CompiledQuery:
        """The program of a recorded (or adopted) plan entry."""
        return CompiledQuery(ent["plan"], ent["decisions"],
                             ent["scan_keys"], mesh=self._mesh,
                             param_dtypes=ent.get("param_dtypes", ()),
                             shard_min_rows=self._shard_min_rows,
                             label=ent.get("label", self._unit_label(key)),
                             decision_nodes=ent.get("decision_nodes"),
                             name_fingerprint=ent.get("name_fp"))

    def _run_unit(self, key, plan, keep_device: bool = False) -> DTable:
        """One compile unit through the record -> compile -> replay
        lifecycle (the pre-segmentation run_query body)."""
        fb0 = len(self.fallback_nodes)
        plan_factory = plan if callable(plan) else (lambda: plan)
        ent = self._plans.get(key) if key is not None else None
        if ent is not None:
            _metrics.PROGRAM_CACHE_HITS.inc()
            if ent["cq"] is not None:                  # steady state
                try:
                    return self._run_compiled(ent["cq"], ent, keep_device)
                except _NOJIT_ERRORS as e:
                    # reachable when precompile_parallel installed the cq
                    # from specs and the real args re-trace differently
                    ent["cq"] = None
                    return self._eager_nojit(ent, e)
                except ReplayMismatch:
                    _metrics.REPLAY_MISMATCHES.inc()
                    self._fp_block = ent.get("fp")
                    self._plans.pop(key, None)
                    ent = None
            elif ent["nojit"]:
                self.last_stats["mode"] = "eager"
                if ent.get("nojit_reason"):
                    self.last_stats["nojit_reason"] = ent["nojit_reason"]
                    self.fallback_nodes.append(
                        f"nojit: {ent['nojit_reason']}")
                return self._eager_ent(ent)
            else:                                      # second sighting
                cq = self._new_cq(key, ent)
                try:
                    out = self._run_compiled(cq, ent, keep_device)
                    ent["cq"] = cq
                    self._publish_cq(ent)
                    return out
                except _NOJIT_ERRORS as e:
                    return self._eager_nojit(ent, e)
                except ReplayMismatch:
                    _metrics.REPLAY_MISMATCHES.inc()
                    self._fp_block = ent.get("fp")
                    self._plans.pop(key, None)
                    ent = None
        # first sighting (or invalidated): eager run, recording the schedule
        _metrics.PROGRAM_CACHE_MISSES.inc()
        plan = plan_factory()
        fp = None
        if key is not None and self._jit_plans:
            pplan, pvalues, pdtypes = parameterize_plan(plan)
            fp = self._shared_fp(pplan)
            if self._adopt_shared(key, fp, tuple(pvalues), tuple(pdtypes)):
                self.last_stats["mode"] = "adopted"
                _metrics.PROGRAMS_ADOPTED.inc()
                return self._run_unit(key, plan, keep_device)
        else:       # uncached one-shot: skip the rewrite, nothing reuses it
            pplan, pvalues, pdtypes = plan, [], []
        self.last_stats["mode"] = "record"
        with TRACER.span("record", label=self._unit_label(key)):
            out, decisions, scan_keys = self.record_plan(pplan,
                                                         tuple(pvalues))
        nodes_attr = self._decision_labels(pplan)
        if nodes_attr:
            # the record pass's decision VALUES are the actuals: the same
            # per-node row counts a later replay reads from its checks
            rows = _node_rows(decisions, nodes_attr,
                              [v for _k, v in decisions])
            if rows:
                self.last_stats["node_rows"] = rows
        if key is not None and self._jit_plans:
            ent = {
                "plan": pplan, "decisions": decisions,
                "scan_keys": scan_keys,
                "params": tuple(pvalues), "param_dtypes": tuple(pdtypes),
                "decision_nodes": nodes_attr,
                "cq": None, "nojit": len(self.fallback_nodes) > fb0,
                "fp": fp, "label": self._unit_label(key),
                "name_fp": self.name_fingerprint(pplan)}
            self._publish_recorded(ent)
            self._plans[key] = ent
            self._fp_block = None
        return out

    def _decision_labels(self, pplan) -> Optional[tuple]:
        """Per-decision TypeName#k attribution of the just-recorded
        schedule (record_plan): verify.node_labels over the parameterized
        plan, so the labels match the session-side plan's labels exactly
        (parameterization rewrites literals, never node structure/order).
        None when no decision carries row semantics."""
        nodes = self._last_record_nodes
        if not nodes or all(n is None for n in nodes):
            return None
        from ..verify import node_labels
        labs = node_labels(pplan)
        return tuple(labs.get(id(n)) if n is not None else None
                     for n in nodes)

    # -- cross-stream program sharing ----------------------------------------
    def _shared_fp(self, pplan) -> Optional[str]:
        """Registry key for a parameterized unit plan, or None when sharing
        is off (mesh runs lower against sharded args; jit disabled)."""
        if self._mesh is not None or not self._jit_plans:
            return None
        return shared_fingerprint(pplan, self._shard_min_rows)

    def _adopt_shared(self, key, fp, pvalues: tuple, pdtypes: tuple) -> bool:
        """Install another stream's entry (schedule + program) for `key`."""
        if fp is None or fp == getattr(self, "_fp_block", None):
            return False
        with _SHARED_LOCK:
            sh = _SHARED_PROGRAMS.get(fp)
            if sh is None or sh.get("volatile") or sh.get("nojit") \
                    or sh.get("param_dtypes") != pdtypes:
                return False
            # system.programs accounting: cross-stream adoptions served
            sh["adoptions"] = sh.get("adoptions", 0) + 1
            ent = {"plan": sh["plan"], "decisions": list(sh["decisions"]),
                   "scan_keys": sh["scan_keys"], "params": pvalues,
                   "param_dtypes": pdtypes, "cq": sh.get("cq"),
                   "decision_nodes": sh.get("decision_nodes"),
                   "nojit": False, "fp": fp,
                   "name_fp": self.name_fingerprint(sh["plan"])}
            scan_meta = dict(sh["scan_meta"])
        for k, v in scan_meta.items():
            self._scan_meta.setdefault(k, v)
        self._plans[key] = ent
        return True

    def _publish_recorded(self, ent) -> None:
        """Publish a freshly recorded schedule; cap-merge with any previous
        stream's so the eventual program serves every stream seen so far."""
        fp = ent.get("fp")
        if fp is None:
            return
        entry = {"plan": ent["plan"], "decisions": list(ent["decisions"]),
                 "scan_keys": ent["scan_keys"],
                 "param_dtypes": ent.get("param_dtypes", ()),
                 "decision_nodes": ent.get("decision_nodes"),
                 "scan_meta": {k: self._scan_meta[k]
                               for k in ent["scan_keys"]
                               if k in self._scan_meta},
                 "cq": None, "nojit": ent.get("nojit", False)}
        with _SHARED_LOCK:
            old = _SHARED_PROGRAMS.get(fp)
            if old is not None and old.get("volatile"):
                return   # proven stream-dependent: stays per-stream forever
            if old is not None \
                    and len(old["decisions"]) == len(entry["decisions"]):
                pairs = list(zip(old["decisions"], entry["decisions"]))
                if any(k1 != k2 for (k1, _), (k2, _) in pairs):
                    entry["volatile"] = True
                elif any(k == "exact" and v1 != v2
                         for (k, v1), (_, v2) in pairs):
                    # structure differs per stream: sharing would replay the
                    # wrong branch — revert to per-stream programs
                    entry["volatile"] = True
                else:
                    merged = [(k, max(v1, v2) if k == "cap" else v1)
                              for (k, v1), (_, v2) in pairs]
                    if merged == old["decisions"] and old.get("cq") is not None:
                        ent["decisions"] = list(merged)
                        ent["cq"] = old["cq"]
                        return          # old program already covers this
                    entry["decisions"] = merged
                    ent["decisions"] = list(merged)
            elif old is not None and len(old["decisions"]) != \
                    len(entry["decisions"]):
                entry["volatile"] = True
            _SHARED_PROGRAMS[fp] = entry

    def _publish_cq(self, ent) -> None:
        """Publish a compiled program for adoption by other streams."""
        fp = ent.get("fp")
        if fp is None or ent.get("cq") is None:
            return
        with _SHARED_LOCK:
            sh = _SHARED_PROGRAMS.get(fp)
            if sh is not None and not sh.get("volatile") \
                    and sh.get("cq") is None \
                    and sh["decisions"] == ent["decisions"]:
                sh["cq"] = ent["cq"]
                # system.programs accounting: compiled programs published
                # under this fingerprint (re-published after cap-merge or
                # quarantine re-record counts again)
                sh["compiles"] = sh.get("compiles", 0) + 1

    def evict_fp(self, fp: Optional[str]) -> int:
        """Drop every LOCAL plan entry (and batched wrapper) published
        under shared fingerprint ``fp`` — the quarantine follow-through:
        after ``strike_shared_program`` evicts the shared entry, the
        owning session must also forget its local copy so the next
        sighting re-records and re-publishes a fresh schedule/program
        instead of replaying the poisoned one. Returns entries dropped.
        Call on the device lane / under the session statement lock (plan
        caches are single-writer there)."""
        if fp is None:
            return 0
        gone = [k for k, ent in self._plans.items()
                if isinstance(ent, dict) and ent.get("fp") == fp]
        for k in gone:
            del self._plans[k]
        for k in [k for k in self._batched if k[0] == fp]:
            del self._batched[k]
        return len(gone)

    def run_param_batch(self, fp: Optional[str], rows: list,
                        ) -> Optional[list]:
        """Serve several COMPATIBLE parameterized queries — same shared
        fingerprint, different hoisted literal values (``rows``) — through
        one batched dispatch (BatchedQuery: one compiled program over a
        stacked parameter matrix). Returns one host-side DTable per row,
        or None when batching is unavailable (no published shared program
        yet, volatile/nojit entry, parameterless plan, mesh/jit off) — the
        caller then serves each query through the normal record/replay
        path. Raises ReplayMismatch when some row's data drifts past the
        recorded schedule; the caller falls back to serial for that batch
        (serial re-records and cap-merges the shared entry as usual)."""
        if fp is None or self._mesh is not None or not self._jit_plans \
                or not rows:
            return None
        with _SHARED_LOCK:
            sh = _SHARED_PROGRAMS.get(fp)
            if sh is None or sh.get("volatile") or sh.get("nojit") \
                    or sh.get("cq") is None or not sh.get("param_dtypes"):
                return None
            cq = sh["cq"]
            scan_meta = dict(sh["scan_meta"])
        if any(len(r) != len(cq.param_dtypes) for r in rows):
            return None
        for k, v in scan_meta.items():
            self._scan_meta.setdefault(k, v)
        cap = bucket(len(rows), minimum=1)
        bq = self._batched.get((fp, cap))
        if bq is None or bq.cq is not cq:
            # a re-published program (cap-merged schedule) obsoletes the
            # batched wrapper: rebuild against the current shared cq
            bq = BatchedQuery(cq, cap)
            self._batched[(fp, cap)] = bq
        self.fallback_nodes = []
        # batch-shape observability: the service's dispatch spans and
        # ExecStats extras report how the stacked matrix actually looked
        self.last_stats = {"batch_rows": len(rows), "batch_cap": cap}
        return bq.run(self._scans_for({"scan_keys": cq.scan_keys}), rows,
                      stats=self.last_stats)

    def _scan_specs(self, ent) -> Optional[tuple]:
        """jax.ShapeDtypeStruct tree mirroring _scans_for(ent) WITHOUT
        uploading anything: shapes come from whichever side already holds
        the table (exec cache, record cache, or segment-output cache).
        None when some scan's shape is not yet known (never recorded)."""
        specs = []
        for k in ent["scan_keys"]:
            src = self._scan_cache.get(k)
            if src is None:
                src = self._scan_cache_rec.get(k)
            if src is None:
                return None
            specs.append(jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), src))
        return tuple(specs)

    def precompile_parallel(self, keys=None, max_workers: Optional[int] = None
                            ) -> dict:
        """Compile every recorded-but-uncompiled plan entry concurrently.

        XLA:TPU compiles one program on one core and outside the GIL, so a
        cold stream's programs compile in max(single) instead of
        sum(serial): five SF1 units took 246.7 s on one worker and 157.6 s
        on eight, the longest program alone (v5e, PERF.md PR 21) — the
        reference pays ~ms of Spark planning per query
        (nds/nds_power.py:124-134) where this engine pays XLA compiles.
        Single-device only: mesh runs
        lower against sharded committed args, which ShapeDtypeStructs here
        do not carry.

        keys: restrict to these plan-entry keys (None = all cached).
        Returns {key: "compiled"|"nojit"|"skipped"} for observability.
        """
        import os as _os
        from concurrent.futures import ThreadPoolExecutor

        if self._mesh is not None:
            return {}
        todo = []
        for k, ent in list(self._plans.items()):
            if not isinstance(ent, dict) or "decisions" not in ent:
                continue
            if keys is not None and k not in keys:
                continue
            if ent.get("cq") is not None or ent.get("nojit"):
                continue
            specs = self._scan_specs(ent)
            if specs is None:
                continue
            todo.append((k, ent, self._new_cq(k, ent), specs))
        if not todo:
            return {}
        workers = max_workers or int(_os.environ.get(
            "NDS_TPU_COMPILE_WORKERS", "8"))
        results: dict = {}

        def one(item):
            k, ent, cq, specs = item
            try:
                cq.precompile(specs)
                return k, ent, cq, "compiled"
            except _NOJIT_ERRORS as e:
                ent["nojit"] = True
                ent["nojit_reason"] = f"{type(e).__name__}: {e}"
                return k, ent, None, "nojit"
            except Exception as e:          # infra hiccup: leave lazy path
                return k, ent, None, f"skipped: {type(e).__name__}"

        with ThreadPoolExecutor(min(workers, len(todo))) as pool:
            for k, ent, cq, status in pool.map(one, todo):
                if cq is not None:
                    ent["cq"] = cq
                    self._publish_cq(ent)
                results[k] = status
        return results

    def compiled_hlo(self, key) -> Optional[str]:
        """Optimized (post-GSPMD) HLO of the steady-state program for `key`
        (the root unit when segmented) — collective-volume inspection for
        the mesh test-suite (SURVEY.md §2 parallelism table: shuffle must
        repartition, not rebuild, sharded fact tables)."""
        for k in ((key, "root"), key):
            ent = self._plans.get(k)
            if ent is not None and ent.get("cq") is not None \
                    and ent["cq"]._fn is not None:
                cq = ent["cq"]
                lowered = cq._fn.lower(*cq._args(self._scans_for(ent),
                                                 ent.get("params", ())))
                return lowered.compile().as_text()
        return None

    def record_plan(self, plan: PlanNode, params: tuple = (),
                    shard_local: bool = False):
        """Eager run that records the capacity schedule; returns
        (result, decisions, scan_keys). scan_keys keep FIRST-TOUCH order
        (plan-traversal order, stream-invariant) — sorting would let
        stream-specific segment fingerprints permute the compiled
        program's argument order and break cross-stream HLO identity.

        shard_local=True records the schedule a sharded-morsel replay will
        consume (shard_exec.ShardedMorselQuery): the shard-local gates
        apply for this call only, so the same session executor records
        both single-chip and per-replica schedules."""
        from ...resilience import FAULTS
        FAULTS.fire("jax.execute")
        rec = _Recorder("record")
        self._rec = rec
        self._touched_scans = {}
        old_params = self._params
        old_shard_local = self._shard_local
        self._params = params
        self._shard_local = self._shard_local or shard_local
        try:
            out = self._eager(plan)
        finally:
            self._rec = None
            self._params = old_params
            self._shard_local = old_shard_local
        self._last_record_nodes = rec.nodes
        return out, rec.decisions, tuple(self._touched_scans)

    def record_plans(self, plans: list, params: tuple = (),
                     shard_local: bool = False):
        """Record several plans under ONE shared decision schedule (shared-
        scan fused morsel groups): the plans run in order with a single
        recorder, and the memo resets per plan exactly like the multi-plan
        replay in CompiledQuery._trace. Returns (outs, decisions,
        scan_keys) — scan_keys is the union in first-touch order across
        plans, so the fused program's argument order is deterministic.
        shard_local: see record_plan."""
        from ...resilience import FAULTS
        FAULTS.fire("jax.execute")
        rec = _Recorder("record")
        self._rec = rec
        self._touched_scans = {}
        old_params = self._params
        old_shard_local = self._shard_local
        self._params = params
        self._shard_local = self._shard_local or shard_local
        outs = []
        try:
            for p in plans:
                outs.append(self._eager(p))
        finally:
            self._rec = None
            self._params = old_params
            self._shard_local = old_shard_local
        return outs, rec.decisions, tuple(self._touched_scans)

    def _load_columns(self, table: str, columns) -> Table:
        from ..executor import load_columns
        return load_columns(self._load_table, table, columns)

    def _run_compiled(self, cq: CompiledQuery, ent,
                      keep_device: bool = False) -> DTable:
        """Run a compiled plan against its device-resident scans. A device
        runtime error (OOM, a program the compiler refused) is the caller's
        to see: it is neither retried here nor answered from the host."""
        return cq.run(self._scans_for(ent), ent.get("params", ()),
                      stats=self.last_stats, keep_device=keep_device)

    def _eager_nojit(self, ent, err: Exception) -> DTable:
        """A unit whose plan cannot trace (_NOJIT_ERRORS) runs eagerly from
        now on — on the host CPU device when the process holds an
        accelerator. That is a fallback off the device and is reported as
        one (``fallback_nodes`` -> Session.last_fallbacks), so strict
        runners fail instead of passing a host-executed query."""
        ent["nojit"] = True
        ent["nojit_reason"] = f"{type(err).__name__}: {err}"
        self.last_stats["mode"] = "eager"
        self.last_stats["nojit_reason"] = ent["nojit_reason"]
        self.fallback_nodes.append(f"nojit: {ent['nojit_reason']}")
        return self._eager_ent(ent)

    def _eager_ent(self, ent) -> DTable:
        """Eager-run a cached entry's (parameterized) plan with its values."""
        old = self._params
        self._params = ent.get("params", ())
        try:
            return self._eager(ent["plan"])
        finally:
            self._params = old

    def _begin(self, plan: PlanNode) -> None:
        """A fresh memo for one plan, and which of its filters carry their
        mask: record pass, replay and every member plan of a fused morsel
        group decide it from the same plan the same way."""
        self._memo = {}
        self._mask_carry = _mask_carrying_filters(plan)

    def _eager(self, plan: PlanNode) -> DTable:
        self._begin(plan)
        if self._eager_device is not None:
            with jax.default_device(self._eager_device):
                return self.execute(plan)
        return self.execute(plan)

    @staticmethod
    def _dtable_bytes(t) -> int:
        """Device bytes of a cached entry (DTable or PackedTable)."""
        return sum(int(leaf.size) * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(t))

    def _account_resident(self, key: str, t: DTable,
                          pinned: Optional[set] = None) -> None:
        """Track an accelerator-resident entry; evict LRU past the budget.

        _resident strictly mirrors _scan_cache (stale keys pruned here), so
        budget math never counts phantom entries."""
        for k in [k for k in self._resident if k not in self._scan_cache]:
            del self._resident[k]
        self._resident.pop(key, None)
        self._resident[key] = self._dtable_bytes(t)
        if self._scan_budget <= 0:
            return
        pinned = pinned or set()
        pinned = pinned | getattr(self, "_pinned_segments", set())
        total = sum(self._resident.values())
        for old in list(self._resident):
            if total <= self._scan_budget:
                break
            if old == key or old in pinned:
                continue
            total -= self._resident.pop(old)
            # evicted entries are unpinned and not inputs of the in-flight
            # run: free their device buffers now (see free_dtable rationale)
            free_dtable(self._scan_cache.pop(old, None))
            if old in self._segment_lru:
                self._segment_lru.remove(old)

    def _scans_for(self, ent) -> dict:
        """Accelerator-resident scan tables for a compiled run (uploaded
        lazily on first use, then shared by every compiled query)."""
        out = {}
        for k in ent["scan_keys"]:
            if k not in self._scan_cache:
                if k.startswith("seg:"):
                    # segment output known only on the record side: move it
                    # to the execution device SHAPE-PRESERVED (capacities are
                    # part of the recorded schedule)
                    rec = self._scan_cache_rec.get(k)
                    if rec is None:
                        raise ReplayMismatch(f"segment output miss: {k}")
                    sharding = self._exec_sharding(rec.capacity) or \
                        jax.devices()[0]
                    self._scan_cache[k] = jax.tree_util.tree_map(
                        lambda x: jax.device_put(x, sharding), rec)
                    out[k] = self._scan_cache[k]
                    continue
                if k not in self._scan_meta:
                    raise ReplayMismatch(f"scan meta miss: {k}")
                table, columns, names = self._scan_meta[k]
                t = self._load_columns(table, columns)
                index = {n: i for i, n in enumerate(t.names)}
                cols = [t.columns[index[c]] for c in columns]
                host = Table(list(names), cols)
                from .device import bucket as _bucket
                self._scan_cache[k] = to_device(
                    host, device=self._exec_sharding(_bucket(host.num_rows)))
            out[k] = self._scan_cache[k]
        pinned = set(ent["scan_keys"])
        for k in ent["scan_keys"]:
            self._account_resident(k, out[k], pinned)
        return out

    def execute(self, node: PlanNode) -> DTable:
        key = id(node)
        if key in self._memo:
            return self._memo[key]
        prev_node = self._cur_node
        self._cur_node = node
        scope = self._scope_labels.get(key)
        try:
            if scope is None:
                result = self._run(node)
            else:
                # trace time only: every instruction of this node carries
                # ".../TypeName#k/<kernel>/..." as its op_name
                with jax.named_scope(scope):
                    result = self._run(node)
        except NotImplementedError as e:
            if self._replay:
                raise
            self.fallback_nodes.append(f"{type(node).__name__}: {e}")
            result = self._host_fallback(node)
        finally:
            self._cur_node = prev_node
        self._memo[key] = result
        return result

    def replay(self, plan):
        """Trace ``plan`` (or the member plans of a fused morsel group, in
        order under the one decision schedule) with a named scope per plan
        node; a replay executor's only entry point."""
        from ..verify import node_labels
        if not isinstance(plan, (list, tuple)):
            self._begin(plan)
            self._scope_labels = node_labels(plan)
            return self.execute(plan)
        outs = []
        for i, p in enumerate(plan):
            # memo resets between member plans, mirroring the per-plan
            # record passes (record_plans) so both consume the shared
            # decision schedule identically
            self._begin(p)
            self._scope_labels = node_labels(p)
            with jax.named_scope(f"member{i}"):
                outs.append(self.execute(p))
        return tuple(outs)

    def execute_to_host(self, node: PlanNode) -> Table:
        return to_host(self.execute(node))

    # -- capacity decisions (record / replay) --------------------------------
    def _decide_cap(self, scalar: jax.Array) -> int:
        """Host-sync a row count for capacity planning; schedule-aware."""
        rec = self._rec
        if rec is None:
            return int(scalar)
        if rec.mode == "record":
            v = int(scalar)
            rec.decisions.append(("cap", v))
            rec.nodes.append(self._cur_node)
            return v
        kind, v = rec.decisions[rec.idx]
        rec.idx += 1
        if kind != "cap":
            raise NotJittable("decision kind drift (cap)")
        rec.checks.append(jnp.asarray(scalar, _I32))
        return v

    def _decide_exact(self, scalar: jax.Array) -> int:
        """Host-sync a value that selects program structure (must replay ==)."""
        rec = self._rec
        if rec is None:
            return int(scalar)
        if rec.mode == "record":
            v = int(scalar)
            rec.decisions.append(("exact", v))
            rec.nodes.append(self._cur_node)
            return v
        kind, v = rec.decisions[rec.idx]
        rec.idx += 1
        if kind != "exact":
            raise NotJittable("decision kind drift (exact)")
        rec.checks.append(jnp.asarray(scalar, _I32))
        return v

    def _decide_exact_lazy(self, fn: Callable[[], jax.Array]) -> int:
        """Exact decision whose traced scalar is computed lazily: when the
        recorded value is falsy, replay skips the computation entirely and
        checks a constant (one-sided verification — taking the general path
        is always correct, so an ineligible-recorded fast path must not pay
        its eligibility probe in the compiled program, nor force a
        re-record when data drifts eligible-ward)."""
        rec = self._rec
        if rec is None:
            return int(fn())
        if rec.mode == "record":
            v = int(fn())
            rec.decisions.append(("exact", v))
            rec.nodes.append(None)   # eligibility probe: no row semantics
            return v
        kind, v = rec.decisions[rec.idx]
        rec.idx += 1
        if kind != "exact":
            raise NotJittable("decision kind drift (exact)")
        rec.checks.append(jnp.asarray(fn(), _I32) if v
                          else jnp.zeros((), _I32))
        return v

    def _decide_table_lazy(self, measure: Callable[[], jax.Array],
                           probe: Callable[[int], jax.Array],
                           bound: int) -> int:
        """The size of the table a fast path builds over a span it measures
        in its input, or 0 where the path is off.

        Two decisions. The eligibility, exact and one-sided as in
        _decide_exact_lazy: a recorded 0 replays a constant, so the general
        path pays for no probe in its compiled program. Under a recorded 1
        only, the span as a ``cap``: the table is the span's ladder bucket,
        and a replay passes iff its traced span is at most that bucket —
        what inflate_schedule, adapt_schedule, the max-merge of shared
        programs and a batched replay's stacked draws already do with any
        capacity. Neither decision is a row count (label None).

        ``measure()`` is the span (0: nothing to address; past ``bound`` the
        path is off, and ``probe`` is not asked at record time);
        ``probe(size)`` is the rest of the eligibility, over a table of
        ``size`` entries."""
        rec = self._rec
        if rec is None or rec.mode == "record":
            span = int(measure())
            size = bucket(span) if 0 < span <= bound else 0
            ok = int(probe(size)) if size else 0
            if rec is not None:
                rec.decisions.append(("exact", ok))
                rec.nodes.append(None)
                if ok:
                    rec.decisions.append(("cap", span))
                    rec.nodes.append(None)
            return size if ok else 0
        kind, ok = rec.decisions[rec.idx]
        rec.idx += 1
        if kind != "exact":
            raise NotJittable("decision kind drift (exact)")
        if not ok:
            rec.checks.append(jnp.zeros((), _I32))
            return 0
        kind, span = rec.decisions[rec.idx]
        rec.idx += 1
        if kind != "cap":
            raise NotJittable("decision kind drift (cap)")
        size = bucket(max(int(span), 1))
        span_t = measure()              # before the probe, as at record time
        rec.checks.append(jnp.asarray(probe(size), _I32))
        rec.checks.append(jnp.asarray(span_t, _I32))
        return size

    def _decide_branch(self, value: bool) -> bool:
        """Record/replay a CAPACITY-DEPENDENT structural branch.

        Capacities drift between record and replay by design (streaming
        raises every cap decision to the morsel bound on a first sighting
        and sizes it from a whole pass's maxima afterwards: inflate_schedule
        / adapt_schedule),
        so a branch gated on `capacity >= X` must take the RECORDED side
        under replay — both sides are semantically correct, and replaying
        the record-time choice keeps the decision schedule aligned. The
        check is a constant equal to the recorded value (trivially passing:
        the branch is a performance choice, not a data property)."""
        rec = self._rec
        if rec is None:
            return value
        if rec.mode == "record":
            rec.decisions.append(("exact", int(value)))
            rec.nodes.append(None)   # performance branch: not a row count
            return value
        kind, v = rec.decisions[rec.idx]
        rec.idx += 1
        if kind != "exact":
            raise NotJittable("decision kind drift (branch)")
        rec.checks.append(jnp.full((), int(v), _I32))
        return bool(v)

    # -- helpers -------------------------------------------------------------
    def _eval(self, expr: BExpr, table: DTable) -> DCol:
        return jexprs.evaluate(expr, table, subquery_eval=self._ectx())

    def _ectx(self) -> "jexprs.EvalCtx":
        return jexprs.EvalCtx(subquery=self._scalar, param=self._param)

    def _param(self, expr, n: int) -> DCol:
        if self._params is None:
            raise NotJittable("parameter slot without bound values")
        v = self._params[expr.index]
        pd = phys_dtype(expr.dtype)
        data = jnp.broadcast_to(jnp.asarray(v, dtype=pd), (n,))
        return DCol(expr.dtype, data, jnp.ones(n, bool))

    def _dense_rank(self, key_data: list, key_valid: list,
                    alive) -> tuple:
        """dense_rank with record-time fast-tier selection (kernels.group_tier):
        the packed single-key sort replaces the multi-operand lax.sort when
        the key domain fits the integer dtype. Static gates keep record and
        replay on the same schedule; the mesh path stays on the generic
        kernel (pack ranges are data-dependent reductions that would force
        GSPMD gathers)."""
        n = int(alive.shape[0])
        if (self._mesh is None and not self._shard_local and key_data
                and all(jnp.issubdtype(d.dtype, jnp.integer)
                        for d in key_data)):
            # the size cutoff is capacity-derived: replay must follow the
            # record-time branch (streaming inflates capacities)
            if self._decide_branch(n >= (1 << 13)) and \
                    self._decide_exact_lazy(
                        lambda: kernels.group_tier(key_data, key_valid,
                                                   alive)):
                return kernels.dense_rank_packsort(key_data, key_valid, alive)
        return kernels.dense_rank(key_data, key_valid, alive)

    def _scalar(self, plan: PlanNode):
        """Uncorrelated scalar subquery -> (value, validity).

        Eager: host python value (validity None == derive from value).
        Replay: traced device scalars so the subquery stays inside the
        compiled program (strings can't: their dictionary would be
        data-dependent at trace time).
        """
        if self._replay:
            dt = self.execute(plan)
            col = decode_col(dt.cols[0])
            if col.dtype == "str" or col.parts is not None:
                raise NotJittable("string scalar subquery under trace")
            perm, cnt = kernels.compaction_perm(dt.alive)
            first = perm[0]
            value = col.data[first]
            valid = (cnt > 0) & col.valid[first]
            return value, valid
        t = to_host(self.execute(plan))
        if t.num_rows == 0:
            return None, None
        col = t.columns[0]
        if not bool(col.validity[0]):
            return None, None
        if col.dtype == "str":
            return col.decode()[0], None
        return np.asarray(col.data)[0].item(), None

    def _host_fallback(self, node: PlanNode) -> DTable:
        repl = {}
        for f in ("child", "left", "right"):
            sub = getattr(node, f, None)
            if isinstance(sub, PlanNode):
                t = to_host(self.execute(sub))
                repl[f] = MaterializedNode(
                    table=t, label=f"device:{f}",
                    out_names=list(sub.out_names), out_dtypes=list(sub.out_dtypes))
        host_node = dataclasses.replace(node, **repl) if repl else node
        if self._params is not None:
            # the numpy expression engine evaluates literals, not slots
            host_node = deparameterize_plan(host_node, list(self._params))
        # expression-embedded subplans can still reference segmented CTEs:
        # the host executor has no segment cache, so materialize them
        vmap = {}
        for n in iter_plan_nodes(host_node):
            if isinstance(n, VirtualScanNode):
                src = self._scan_cache_rec.get(n.key,
                                               self._scan_cache.get(n.key))
                if src is None:
                    raise RuntimeError(f"segment {n.key!r} not materialized")
                vmap[id(n)] = MaterializedNode(
                    table=to_host(src), label=n.key,
                    out_names=list(n.out_names),
                    out_dtypes=list(n.out_dtypes))
        if vmap:
            host_node = replace_plan_nodes(host_node, vmap)
        host = HostExecutor(self._load_table)
        return to_device(host.execute(host_node))

    def _maybe_compact(self, t: DTable, carry_mask: bool = False) -> DTable:
        """Compact ``t`` to its survivors' bucket when that is under half its
        capacity: a 2-operand sort over the capacity and a gather of every
        column, repaid by every later kernel that is sized by the capacity.

        ``carry_mask`` (a FilterNode that _mask_carrying_filters exempts)
        returns ``t`` with its narrowed alive mask instead. The rule, read
        from the plan and the process's x64 setting before anything runs:
        every consumer of the filter's result, through ProjectNodes only, is
        a keyless AggregateNode (no group_exprs, no rollup) whose aggregates
        are non-distinct count_star / count / sum / min / max / avg over
        operands of an INTEGER physical dtype (int, date, scaled-int
        decimals; avg only under x64). kernels._seg reduces those by an
        alive-masked reduce into the ONE static group at 0.1-0.5 ms per 1M
        rows on a v5e; the sort and gather it would repay cost 39 ms at 4M
        rows. Outside the rule, and compacting as ever: a float operand (and
        avg without x64, which sums in float) keeps the segment path in both
        modes, because its reduction order differs between the paths in the
        last ULPs, and an n-row scatter into bucket(1) segments is the one
        consumer a compaction does repay; a distinct aggregate ranks its
        operand (distinct_within_group sorts the capacity); a string operand
        goes through _agg_string. Any other parent — a join, a sort, a
        window, a keyed aggregate, the root, an expression — wants the
        compacted table.

        The cap decision is recorded either way, so every schedule (record,
        replay, inflate_schedule, adapt_schedule, the mesh replay) keeps its
        positions."""
        count_t = t.count()
        count = self._decide_cap(count_t)
        cap = bucket(count)
        if self._mesh is not None or self._shard_local:
            # compaction is a global permutation (sort/cumsum/gather): under
            # SPMD it would force GSPMD to all-gather the sharded buffer.
            # Alive-masked ops stay shard-local, so larger masked capacities
            # beat rebuilding the table across the ICI. (The cap decision
            # above still records, keeping schedules mode-agnostic.)
            # Shard-local replays skip it for the same schedule shape: the
            # record pass sees one replica-sized slice, and a capacity-
            # relative branch would drift per shard.
            return t
        if carry_mask:
            self.mask_carried += 1
            return t
        if t.capacity <= 2 * cap:
            return t
        perm, _ = kernels.compaction_perm(t.alive)
        perm = perm[:cap]
        cols = _gather_cols(t.cols, perm)
        alive = jnp.arange(cap, dtype=_I32) < count_t
        return DTable(t.names, cols, alive)

    # -- node dispatch -------------------------------------------------------
    def _run(self, node: PlanNode) -> DTable:
        if isinstance(node, MaterializedNode):
            return to_device(node.table)
        if isinstance(node, VirtualScanNode):
            return self._run_virtual(node)
        if isinstance(node, ScanNode):
            return self._run_scan(node)
        if isinstance(node, FilterNode):
            child = self.execute(node.child)
            mask = self._eval(node.predicate, child)
            alive = kernels.filter_alive(child.alive, mask.data, mask.valid)
            return self._maybe_compact(
                DTable(list(node.out_names), child.cols, alive),
                carry_mask=id(node) in self._mask_carry)
        if isinstance(node, ProjectNode):
            child = self.execute(node.child)
            cols = [self._eval(e, child) for e in node.exprs]
            return DTable(list(node.out_names), cols, child.alive)
        if isinstance(node, JoinNode):
            return self._run_join(node)
        if isinstance(node, AggregateNode):
            return self._run_aggregate(node)
        if isinstance(node, WindowNode):
            return self._run_window(node)
        if isinstance(node, SortNode):
            return self._run_sort(node)
        if isinstance(node, LimitNode):
            child = self.execute(node.child)
            alive = kernels.limit_alive(child.alive, node.n)
            return self._maybe_compact(DTable(list(node.out_names),
                                              child.cols, alive))
        if isinstance(node, DistinctNode):
            child = self.execute(node.child)
            alive = self._distinct_alive(child, list(range(len(child.cols))))
            return self._maybe_compact(DTable(list(node.out_names),
                                              child.cols, alive))
        if isinstance(node, SetOpNode):
            return self._run_setop(node)
        raise NotImplementedError(type(node).__name__)

    def _run_setop(self, node: SetOpNode) -> DTable:
        left = self.execute(node.left)
        right = self.execute(node.right)
        names = list(node.out_names)
        both = _concat_dtables([left, right], names)
        if node.op == "union":
            if node.all:
                return both
            alive = self._distinct_alive(both, list(range(len(both.cols))))
            return self._maybe_compact(DTable(names, both.cols, alive))
        # intersect / except: distinct-row semantics (mirrors host ops.set_op)
        lcap = left.capacity
        n = both.capacity
        iota = jnp.arange(n, dtype=_I32)
        is_left = iota < lcap
        keys = [rank_key(c) for c in both.cols]
        valids = [c.valid for c in both.cols]
        gid, _ = self._dense_rank(keys, valids, both.alive)
        safe_gid = jnp.where(both.alive, gid, n)
        in_left = jnp.zeros(n + 1, bool).at[
            jnp.where(is_left, safe_gid, n)].set(True)
        in_right = jnp.zeros(n + 1, bool).at[
            jnp.where(~is_left, safe_gid, n)].set(True)
        keep = (in_left & in_right) if node.op == "intersect" \
            else (in_left & ~in_right)
        first_left = jnp.full(n + 1, n, dtype=_I32).at[
            jnp.where(both.alive & is_left, gid, n)].min(iota)
        alive = both.alive & is_left & keep[jnp.clip(gid, 0, n)] & \
            (first_left[jnp.clip(gid, 0, n)] == iota)
        return self._maybe_compact(DTable(names, both.cols, alive))

    def _run_virtual(self, node: VirtualScanNode) -> DTable:
        """A segmented-CTE output: resolved against the segment cache (the
        orchestrator in run_query materializes segments before consumers)."""
        self._touched_scans.setdefault(node.key)
        cache = self._scan_cache if self._replay else self._scan_cache_rec
        t = cache.get(node.key)
        if t is None:
            if self._replay:
                raise NotJittable(f"segment {node.key!r} missing under trace")
            other = self._scan_cache.get(node.key)
            if other is None:
                raise RuntimeError(      # orchestration bug, never fallback
                    f"segment {node.key!r} not materialized")
            # bridge device output to the record-side device SHAPE-PRESERVED
            dev = self._eager_device or jax.devices()[0]
            cache[node.key] = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, dev), other)
            t = cache[node.key]
        return DTable(list(node.out_names), t.cols, t.alive)

    def _run_scan(self, node: ScanNode) -> DTable:
        cache_key = node.table + "//" + ",".join(node.columns)
        cache = self._scan_cache if self._replay else self._scan_cache_rec
        if cache_key not in cache:
            if self._replay:
                raise NotJittable(f"scan {cache_key!r} missing under trace")
            t = self._load_columns(node.table, node.columns)
            index = {n: i for i, n in enumerate(t.names)}
            cols = [t.columns[index[c]] for c in node.columns]
            cache[cache_key] = to_device(Table(list(node.out_names), cols),
                                         device=self._eager_device)
        self._touched_scans.setdefault(cache_key)
        self._scan_meta[cache_key] = (node.table, list(node.columns),
                                      list(node.out_names))
        cached = cache[cache_key]
        if isinstance(cached, PackedTable):
            # packed morsel upload: column slicing/bitcasts fuse into the
            # compiled program (see PackedTable)
            cached = unpack_table(cached)
        out = DTable(list(node.out_names), cached.cols, cached.alive)
        self.scan_rows += out.capacity
        return out

    # -- sort / distinct -----------------------------------------------------
    def _run_sort(self, node: SortNode) -> DTable:
        child = self.execute(node.child)
        key_cols = [self._eval(k.expr, child) for k in node.keys]
        key_data = [rank_key(c) for c in key_cols]
        key_valid = [c.valid for c in key_cols]
        perm = kernels.sort_perm(key_data, key_valid,
                                 kernels.sort_specs(node.keys), child.alive)
        cols = _gather_cols(child.cols, perm)
        return DTable(list(node.out_names), cols, child.alive[perm])

    def _distinct_alive(self, t: DTable, col_idx: list[int]) -> jax.Array:
        keys = [rank_key(t.cols[i]) for i in col_idx]
        valids = [t.cols[i].valid for i in col_idx]
        gid, _ = self._dense_rank(keys, valids, t.alive)
        n = t.capacity
        iota = jnp.arange(n, dtype=_I32)
        first = jnp.full(n + 1, n, dtype=_I32).at[
            jnp.where(t.alive, gid, n)].min(iota)
        return t.alive & (first[jnp.clip(gid, 0, n)] == iota)

    # -- window functions ----------------------------------------------------
    def _run_window(self, node: WindowNode) -> DTable:
        child = self.execute(node.child)
        out_cols = list(child.cols)
        for wf in node.funcs:
            out_cols.append(self._window_one(wf, child))
        return DTable(list(node.out_names), out_cols, child.alive)

    def _window_one(self, wf: WindowFunc, child: DTable) -> DCol:
        n = child.capacity
        pcols = [self._eval(e, child) for e in wf.partition_by]
        gid, _ = self._dense_rank([rank_key(c) for c in pcols],
                                  [c.valid for c in pcols], child.alive)
        arg_col = None if wf.arg is None else widen_col(
            self._eval(wf.arg, child))
        if arg_col is not None and arg_col.dtype == "str":
            raise NotImplementedError("window function over strings (device)")
        func = wf.func
        if arg_col is None:
            if func in ("count", "count_star"):
                func = "count_star"
            arg = None
        else:
            arg = (arg_col.canon().data, arg_col.valid)

        if not wf.order_by:
            if func in ("rank", "dense_rank", "row_number"):
                raise NotImplementedError(f"{func} requires ORDER BY")
            vals, valid = kernels.agg_apply(gid, child.alive, func, arg, n)
            safe = jnp.clip(gid, 0, n - 1)
            data, dvalid = vals[safe], valid[safe]
        else:
            ocols = [self._eval(k.expr, child) for k in wf.order_by]
            okd = [rank_key(c) for c in ocols]
            okv = [c.valid for c in ocols]
            specs = ((True, None),) + kernels.sort_specs(wf.order_by)
            perm = kernels.sort_perm([gid] + okd,
                                     [jnp.ones(n, bool)] + okv,
                                     specs, child.alive)
            sarg = None if arg is None else (arg[0][perm], arg[1][perm])
            vals_s, valid_s = kernels.window_ordered_core(
                gid[perm], [d[perm] for d in okd], [v[perm] for v in okv],
                sarg, func)
            data, dvalid = kernels.unscatter(perm, (vals_s, valid_s))
        if arg_col is not None and is_dec(arg_col.dtype) and wf.func == "avg":
            data = data / 10.0 ** dec_scale(arg_col.dtype)  # descale
        pd = phys_dtype(wf.dtype)
        return DCol(wf.dtype, data.astype(pd), dvalid & child.alive)

    # -- aggregate -----------------------------------------------------------
    def _run_aggregate(self, node: AggregateNode) -> DTable:
        child = self.execute(node.child)
        grouping_sets = _grouping_sets(node)
        if self._sorted_agg_eligible(node, child, grouping_sets):
            return self._aggregate_sorted(node, child, grouping_sets)
        pieces = [self._aggregate_one_sharded(node, child, keep)
                  if self._mesh_agg_eligible(node, keep)
                  else self._aggregate_one(node, child, keep)
                  for keep in grouping_sets]
        if len(pieces) == 1:
            return pieces[0]
        return _concat_dtables(pieces, list(node.out_names))

    def _sorted_agg_eligible(self, node: AggregateNode, child: DTable,
                             grouping_sets: list) -> bool:
        """Static gate for the sorted aggregation path: ONE key sort shared
        by every rollup prefix level, within-group scans instead of the
        serialized segment scatters, S-sized gathers for output assembly.
        Single-device only (the mesh path has its own shard-local plan, and
        sharded-morsel replays must not re-probe per-shard key ranges)."""
        if self._mesh is not None or self._shard_local:
            return False
        if not node.group_exprs:
            return False          # global aggregate: masked reduces suffice
        for s in node.aggs:
            if s.distinct or s.func not in (
                    "count_star", "count", "sum", "min", "max", "avg",
                    "stddev_samp"):
                return False
            if s.arg is not None and s.arg.dtype == "str":
                return False
        # capacity cutoff LAST (after the static gates) so the recorded
        # branch sits at a deterministic schedule position; replay follows
        # the record-time choice (streaming inflates capacities)
        return self._decide_branch(child.capacity >= (1 << 13))

    def _aggregate_sorted(self, node: AggregateNode, child: DTable,
                          grouping_sets: list) -> DTable:
        n = child.capacity
        alive = child.alive
        group_cols = [self._eval(e, child) for e in node.group_exprs]
        keys = [rank_key(c) for c in group_cols]
        kvalids = [c.valid for c in group_cols]
        # aggregate arguments widen off narrow lanes: the within-group scan
        # accumulates in the payload dtype, and an i32 sum over a morsel of
        # narrow-lane values would overflow (group KEYS stay narrow)
        arg_cols = [None if s.arg is None else widen_col(
            self._eval(s.arg, child)) for s in node.aggs]
        x64 = jax.config.read("jax_enable_x64")
        fd = jnp.float64 if x64 else jnp.float32

        # the pack probe only handles integer rank keys (float group keys —
        # legal SQL — have no iinfo range); static gate so record and replay
        # stay on one schedule
        int_keys = all(jnp.issubdtype(k.dtype, jnp.integer) for k in keys)
        tier = self._decide_exact_lazy(
            lambda: kernels.group_tier(keys, kvalids, alive)) if int_keys \
            else self._decide_exact(jnp.zeros((), _I32))

        # ---- ONE sort: keys (packed when possible) + agg args as payload,
        # deduplicated by expression so SUM(x)/AVG(x) carry x once
        payloads: list = []
        pay_idx: list = []        # per spec: index into payloads or None
        seen_args: dict[str, int] = {}
        for s, ac in zip(node.aggs, arg_cols):
            if ac is None:
                pay_idx.append(None)
                continue
            akey = repr(s.arg)
            if akey in seen_args:
                pay_idx.append(seen_args[akey])
                continue
            seen_args[akey] = len(payloads)
            pay_idx.append(len(payloads))
            payloads.append(ac.canon().data)
            payloads.append(ac.valid)
        iota = jnp.arange(n, dtype=_I32)
        if tier:
            norms, ranges, _ = kernels._key_ranges(keys, kvalids, alive)
            pd = kernels._pack_dtype()
            pack = jnp.zeros(n, pd)
            for norm, r in zip(norms, ranges):
                pack = pack * r + norm.astype(pd)
            key_ops = [jnp.where(alive, pack, jnp.iinfo(pd).max)]
            nkey_ops = 1
        else:
            ranges = None
            key_ops = [(~alive).astype(_I32)]
            for d, v in zip(keys, kvalids):
                key_ops.append((~v).astype(_I32))
                key_ops.append(jnp.where(v & alive, d,
                                         jnp.zeros((), d.dtype)))
            nkey_ops = len(key_ops)
        out = lax.sort(tuple(key_ops) + tuple(payloads) + (iota,),
                       num_keys=nkey_ops, is_stable=True)
        sorted_keys = out[:nkey_ops]
        sorted_pays = out[nkey_ops:-1]
        perm = out[-1]
        iota_s = iota
        alive_sorted = iota_s < jnp.sum(alive.astype(_I32))

        def level_new_group(k: int) -> jax.Array:
            first = alive_sorted & (iota_s == 0)
            if k == 0:
                return first
            if ranges is not None:
                stride = jnp.ones((), sorted_keys[0].dtype)
                for r in ranges[k:]:
                    stride = stride * r
                ck = sorted_keys[0] // stride
                diff = jnp.concatenate([jnp.ones(1, bool),
                                        ck[1:] != ck[:-1]])
            else:
                diff = jnp.zeros(n, bool)
                for i in range(k):
                    for op in (sorted_keys[1 + 2 * i],
                               sorted_keys[2 + 2 * i]):
                        diff = diff | jnp.concatenate(
                            [jnp.ones(1, bool), op[1:] != op[:-1]])
            return (alive_sorted & diff) | first

        pieces: list[DTable] = []
        for keep in grouping_sets:
            k = len(keep)
            if k == 0:
                # grand total: one group — the masked-reduce path is exact
                # and cheap, and handles the empty-input one-row semantics
                pieces.append(self._aggregate_one(node, child, keep))
                continue
            new_group = level_new_group(k)
            gid_sorted = jnp.cumsum(new_group.astype(_I32)) - 1
            num_groups_t = jnp.max(jnp.where(alive_sorted, gid_sorted, -1)) + 1
            cap_out = bucket(max(self._decide_cap(num_groups_t), 1))
            is_end = kernels.group_ends(new_group, alive_sorted)
            end_perm, _ = kernels.compaction_perm(is_end)
            sel = end_perm[:cap_out]
            orig = perm[sel]
            alive_out = jnp.arange(cap_out, dtype=_I32) < num_groups_t

            out_cols: list[DCol] = []
            for i, gc in enumerate(group_cols):
                if i < k:
                    cd = gc.canon().data
                    # sort/scan ran on codes; decode the group-sized output
                    out_cols.append(decode_col(DCol(
                        gc.dtype, cd[orig], gc.valid[orig] & alive_out,
                        gc.dictionary, codebook=gc.codebook)))
                else:
                    out_cols.append(DCol(
                        gc.dtype, jnp.zeros(cap_out, phys_dtype(gc.dtype)),
                        jnp.zeros(cap_out, bool), gc.dictionary))

            ones_i = jnp.where(alive_sorted, 1, 0).astype(_I32)
            for spec, ac, pi in zip(node.aggs, arg_cols, pay_idx):
                if spec.func == "count_star":
                    cnt_s = kernels.sorted_agg_scan(ones_i, new_group,
                                                    jnp.add)
                    int_out = jnp.int64 if x64 else _I32
                    vals = cnt_s[sel].astype(int_out)
                    out_cols.append(DCol(spec.dtype,
                                         vals.astype(phys_dtype(spec.dtype)),
                                         jnp.ones(cap_out, bool)))
                    continue
                data_s = sorted_pays[pi]
                valid_s = sorted_pays[pi + 1] & alive_sorted
                contrib_i = valid_s.astype(
                    jnp.int64 if x64 else _I32)
                cnt_s = kernels.sorted_agg_scan(contrib_i, new_group, jnp.add)
                cnt_sel = cnt_s[sel]
                func = spec.func
                if func == "count":
                    out_cols.append(DCol(
                        spec.dtype, cnt_sel.astype(phys_dtype(spec.dtype)),
                        jnp.ones(cap_out, bool)))
                    continue
                int_in = jnp.issubdtype(data_s.dtype, jnp.integer)
                if func in ("sum", "avg"):
                    acc = data_s.dtype if (int_in and (func == "sum" or x64)) \
                        else fd
                    w = jnp.where(valid_s, data_s.astype(acc),
                                  jnp.zeros((), acc))
                    sum_sel = kernels.sorted_agg_scan(w, new_group,
                                                      jnp.add)[sel]
                    if func == "sum":
                        vals = sum_sel
                        dvalid = cnt_sel > 0
                    else:
                        vals = (sum_sel.astype(fd) /
                                jnp.maximum(cnt_sel, 1).astype(fd))
                        dvalid = cnt_sel > 0
                elif func in ("min", "max"):
                    ext = kernels._extreme(data_s.dtype, func)
                    w = jnp.where(valid_s, data_s, ext)
                    op = jnp.minimum if func == "min" else jnp.maximum
                    vals = kernels.sorted_agg_scan(w, new_group, op)[sel]
                    dvalid = cnt_sel > 0
                    vals = jnp.where(dvalid, vals,
                                     jnp.zeros((), data_s.dtype))
                else:           # stddev_samp
                    zf = jnp.where(valid_s, data_s, 0).astype(fd)
                    s1 = (kernels.sorted_agg_scan(
                        jnp.where(valid_s, data_s,
                                  jnp.zeros((), data_s.dtype)), new_group,
                        jnp.add)[sel].astype(fd) if int_in and x64 else
                        kernels.sorted_agg_scan(zf, new_group, jnp.add)[sel])
                    s2 = kernels.sorted_agg_scan(zf * zf, new_group,
                                                 jnp.add)[sel]
                    nf = cnt_sel.astype(fd)
                    var = (s2 - s1 * s1 / jnp.maximum(nf, 1.0)) / \
                        jnp.maximum(nf - 1.0, 1.0)
                    vals = jnp.sqrt(jnp.maximum(var, 0.0))
                    dvalid = cnt_sel > 1
                if ac is not None and is_dec(ac.dtype) and \
                        spec.func in ("avg", "stddev_samp"):
                    vals = vals / 10.0 ** dec_scale(ac.dtype)
                out_cols.append(DCol(spec.dtype,
                                     vals.astype(phys_dtype(spec.dtype)),
                                     dvalid & alive_out))
            if node.rollup:
                gid_val = sum(1 << (len(node.group_exprs) - 1 - i)
                              for i in range(len(node.group_exprs))
                              if i >= k)
                out_cols.append(DCol("int",
                                     jnp.full(cap_out, gid_val,
                                              phys_dtype("int")),
                                     jnp.ones(cap_out, bool)))
            pieces.append(DTable(list(node.out_names), out_cols, alive_out))
        if len(pieces) == 1:
            return pieces[0]
        return _concat_dtables(pieces, list(node.out_names))

    def _mesh_agg_eligible(self, node: AggregateNode, keep: list[int]) -> bool:
        """Shard-local grouped aggregation (partial agg + bounded-partials
        all_gather + replicated merge — the Spark partial/final aggregate
        plan, SURVEY.md §2 parallelism table). Static eligibility so record
        and replay take the same branch."""
        if self._mesh is None or not keep:
            return False
        for s in node.aggs:
            if s.distinct or s.func not in ("sum", "count", "count_star",
                                            "min", "max", "avg"):
                return False
        return True

    def _aggregate_one_sharded(self, node: AggregateNode, child: DTable,
                               keep: list[int]) -> DTable:
        """GROUP BY over row-sharded data WITHOUT gathering the fact table:
        each shard dense-ranks its local rows and aggregates into n_partial
        slots; only the bounded partials ride the ICI (all_gather), and the
        replicated merge re-ranks 8*n_partial candidate groups. GSPMD's
        fallback for the same plan all-gathers the whole child (measured:
        q3-class group-by gathered cap-sized s32 buffers)."""
        from jax import shard_map
        from jax.sharding import PartitionSpec

        from .device import string_rank_maps

        mesh = self._mesh
        axis = mesh.axis_names[0]
        Pax, Prep = PartitionSpec(axis), PartitionSpec()
        group_cols = [self._eval(e, child) for e in node.group_exprs]
        active = [group_cols[i] for i in keep]
        rank_keys = tuple(rank_key(c) for c in active)
        kvalids = tuple(c.valid for c in active)
        codes = tuple(c.canon().data for c in active)
        alive = child.alive

        # per-spec local inputs + merge recipes (streaming.py-style
        # decomposition into mergeable pieces)
        spec_args: list = []
        recipes: list[tuple] = []     # (kind, extra) per spec
        for spec in node.aggs:
            if spec.arg is None:
                spec_args.append(None)
                recipes.append(("count_star", None))
                continue
            ac = widen_col(self._eval(spec.arg, child))
            post = None
            data, valid = ac.canon().data, ac.valid
            if ac.dtype == "str":
                if spec.func == "count":
                    recipes.append(("count", None))
                elif spec.func in ("min", "max"):
                    ranks, rank_to_code = string_rank_maps(ac.dictionary)
                    data = jexprs._lut_gather(ac.data, ranks)
                    post = ("str", rank_to_code, ac.dictionary)
                    recipes.append((spec.func, post))
                else:
                    raise NotImplementedError(
                        f"device {spec.func} over strings")
            elif spec.func == "avg":
                if is_dec(ac.dtype):
                    post = ("dec_avg", dec_scale(ac.dtype))
                recipes.append(("avg", post))
            else:
                if spec.func == "sum" and (ac.dtype == "int"
                                           or is_dec(ac.dtype)):
                    data = data.astype(phys_dtype("int"))
                recipes.append((spec.func, None))
            spec_args.append((data, valid))
        spec_args = tuple(spec_args)

        nsh = mesh.devices.size

        def probe(rk, kv, al):
            _, ng = kernels.dense_rank(list(rk), list(kv), al)
            return ng.reshape(1)

        ng_sh = shard_map(probe, mesh=mesh, in_specs=(Pax, Pax, Pax),
                          out_specs=Pax, check_vma=False)(
            rank_keys, kvalids, alive)
        n_partial = bucket(max(self._decide_cap(jnp.max(ng_sh)), 1))
        cap_out = n_partial * nsh

        def seg_sum(vals, mask, m_gid, occ):
            sg = jnp.where(occ & mask, m_gid, cap_out)
            return jax.ops.segment_sum(jnp.where(occ & mask, vals, 0), sg,
                                       num_segments=cap_out + 1)[:cap_out]

        def seg_any(mask, m_gid, occ):
            sg = jnp.where(occ, m_gid, cap_out)
            return jax.ops.segment_max(
                (occ & mask).astype(_I32), sg,
                num_segments=cap_out + 1)[:cap_out] > 0

        def local(rk, kv, cd, al, sa):
            gid, _ = kernels.dense_rank(list(rk), list(kv), al)
            occ = jnp.zeros(n_partial + 1, bool).at[
                jnp.where(al & (gid < n_partial), gid, n_partial)
            ].set(True)[:n_partial]
            rreps, creps, cvals = [], [], []
            for r, v, c in zip(rk, kv, cd):
                rr, _ = kernels.group_representatives(gid, al, r, v,
                                                      n_partial)
                cc, vv = kernels.group_representatives(gid, al, c, v,
                                                       n_partial)
                rreps.append(rr)
                creps.append(cc)
                cvals.append(vv)
            parts = []          # flat pieces per recipe, (vals, valid)
            for (kind, _x), a in zip(recipes, sa):
                if kind == "count_star":
                    v, _ = kernels.agg_apply(gid, al, "count_star", None,
                                             n_partial)
                    parts.append((v, jnp.ones(n_partial, bool)))
                elif kind == "count":
                    v, _ = kernels.agg_apply(gid, al, "count", a, n_partial)
                    parts.append((v, jnp.ones(n_partial, bool)))
                elif kind == "avg":
                    s, sv = kernels.agg_apply(
                        gid, al, "sum",
                        (a[0].astype(phys_dtype("int"))
                         if jnp.issubdtype(a[0].dtype, jnp.integer)
                         else a[0], a[1]), n_partial)
                    c, _ = kernels.agg_apply(gid, al, "count", a, n_partial)
                    parts.append((s, sv))
                    parts.append((c, jnp.ones(n_partial, bool)))
                else:           # sum / min / max
                    v, vv = kernels.agg_apply(gid, al, kind, a, n_partial)
                    parts.append((v, vv))
            ga = lambda x: jax.lax.all_gather(x, axis, tiled=True)  # noqa: E731
            g_occ = ga(occ)
            g_rr = [ga(x) for x in rreps]
            g_cc = [ga(x) for x in creps]
            g_cv = [ga(x) for x in cvals]
            g_parts = [(ga(v), ga(m)) for v, m in parts]
            m_gid, _ = kernels.dense_rank(g_rr, g_cv, g_occ)
            out_codes, out_cvals = [], []
            for cc, vv in zip(g_cc, g_cv):
                oc, ov = kernels.group_representatives(m_gid, g_occ, cc, vv,
                                                       cap_out)
                out_codes.append(oc)
                out_cvals.append(ov)
            out_occ = jnp.zeros(cap_out + 1, bool).at[
                jnp.where(g_occ, m_gid, cap_out)].set(True)[:cap_out]
            merged = []
            pi = 0
            for kind, _x in recipes:
                if kind in ("count_star", "count"):
                    gv, gm = g_parts[pi]
                    pi += 1
                    merged.append((seg_sum(gv, gm, m_gid, g_occ),
                                   jnp.ones(cap_out, bool)))
                elif kind == "sum":
                    gv, gm = g_parts[pi]
                    pi += 1
                    merged.append((seg_sum(gv, gm, m_gid, g_occ),
                                   seg_any(gm, m_gid, g_occ)))
                elif kind in ("min", "max"):
                    gv, gm = g_parts[pi]
                    pi += 1
                    ext = kernels._extreme(gv.dtype, kind)
                    sg = jnp.where(g_occ & gm, m_gid, cap_out)
                    seg = jax.ops.segment_min if kind == "min" \
                        else jax.ops.segment_max
                    vals = seg(jnp.where(g_occ & gm, gv, ext), sg,
                               num_segments=cap_out + 1)[:cap_out]
                    valid = seg_any(gm, m_gid, g_occ)
                    merged.append((jnp.where(valid, vals,
                                             jnp.zeros((), gv.dtype)), valid))
                else:           # avg: sum piece + count piece
                    gs, gsm = g_parts[pi]
                    gc, gcm = g_parts[pi + 1]
                    pi += 2
                    sm = seg_sum(gs, gsm, m_gid, g_occ)
                    cm = seg_sum(gc, gcm, m_gid, g_occ)
                    fdt = jnp.float64 if jax.config.read("jax_enable_x64") \
                        else jnp.float32
                    vals = sm.astype(fdt) / jnp.maximum(cm, 1).astype(fdt)
                    merged.append((vals, cm > 0))
            return (tuple(out_codes), tuple(out_cvals), out_occ,
                    tuple(x for pair in merged for x in pair))

        out_codes, out_cvals, out_occ, flat = shard_map(
            local, mesh=mesh, in_specs=(Pax, Pax, Pax, Pax, Pax),
            out_specs=(Prep, Prep, Prep, Prep), check_vma=False)(
            rank_keys, kvalids, codes, alive, spec_args)
        merged = [(flat[i], flat[i + 1]) for i in range(0, len(flat), 2)]

        out_cols: list[DCol] = []
        keep_set = set(keep)
        ai = 0
        for i, gc in enumerate(group_cols):
            if i in keep_set:
                out_cols.append(decode_col(DCol(
                    gc.dtype, out_codes[ai], out_cvals[ai], gc.dictionary,
                    codebook=gc.codebook)))
                ai += 1
            else:
                out_cols.append(DCol(gc.dtype,
                                     jnp.zeros(cap_out, phys_dtype(gc.dtype)),
                                     jnp.zeros(cap_out, bool), gc.dictionary))
        for spec, (kind, post), (vals, valid) in zip(node.aggs, recipes,
                                                     merged):
            if isinstance(post, tuple) and post[0] == "str":
                codes_out = jexprs._lut_gather(vals.astype(_I32), post[1])
                out_cols.append(DCol("str", codes_out, valid, post[2]))
                continue
            if isinstance(post, tuple) and post[0] == "dec_avg":
                vals = vals / 10.0 ** post[1]
            out_cols.append(DCol(spec.dtype,
                                 vals.astype(phys_dtype(spec.dtype)), valid))
        if node.rollup:
            gid_val = sum(1 << (len(node.group_exprs) - 1 - i)
                          for i in range(len(node.group_exprs))
                          if i not in keep_set)
            out_cols.append(DCol("int",
                                 jnp.full(cap_out, gid_val,
                                          phys_dtype("int")),
                                 jnp.ones(cap_out, bool)))
        return DTable(list(node.out_names), out_cols, out_occ)

    def _aggregate_one(self, node: AggregateNode, child: DTable,
                       keep: list[int]) -> DTable:
        group_cols = [self._eval(e, child) for e in node.group_exprs]
        active = [group_cols[i] for i in keep]
        gid, num_groups_t = self._dense_rank(
            [rank_key(c) for c in active], [c.valid for c in active],
            child.alive)
        if active:
            num_groups = self._decide_cap(num_groups_t)
        else:
            # a global aggregate (incl. a rollup's grand-total grouping set)
            # has ONE group by construction, and over empty input still
            # yields its one row: a static 1, never a recorded capacity
            # decision (streaming.inflate_schedule would raise it to the
            # morsel bound and push every agg_apply onto the scatter path)
            num_groups = 1
            num_groups_t = jnp.maximum(num_groups_t, 1)
        alive_for_agg = child.alive
        cap_out = bucket(max(num_groups, 1))

        out_cols: list[DCol] = []
        keep_set = set(keep)
        for i, gc in enumerate(group_cols):
            if i in keep_set:
                vals, valid = kernels.group_representatives(
                    gid, alive_for_agg, gc.canon().data, gc.valid, cap_out)
                # grouping ran on codes (rank_key); the group-output
                # representative is the decode site — group-sized, not
                # row-sized
                out_cols.append(decode_col(DCol(gc.dtype, vals, valid,
                                                gc.dictionary,
                                                codebook=gc.codebook)))
            else:  # rolled-up column: NULL
                out_cols.append(DCol(gc.dtype,
                                     jnp.zeros(cap_out, phys_dtype(gc.dtype)),
                                     jnp.zeros(cap_out, bool), gc.dictionary))

        agg_results = self._compute_aggs(node.aggs, child, gid,
                                         alive_for_agg, cap_out)
        out_cols.extend(agg_results)
        if node.rollup:
            gid_val = sum(1 << (len(node.group_exprs) - 1 - i)
                          for i in range(len(node.group_exprs))
                          if i not in keep_set)
            out_cols.append(DCol("int",
                                 jnp.full(cap_out, gid_val, phys_dtype("int")),
                                 jnp.ones(cap_out, bool)))
        alive = jnp.arange(cap_out, dtype=_I32) < num_groups_t
        names = list(node.out_names)
        return DTable(names, out_cols, alive)

    def _compute_aggs(self, specs: list[AggSpec], child: DTable,
                      gid: jax.Array, alive: jax.Array,
                      cap_out: int) -> list[DCol]:
        out: list[DCol] = []
        for spec in specs:
            arg_col = None if spec.arg is None else widen_col(
                self._eval(spec.arg, child))
            use_alive = alive
            if spec.distinct and arg_col is not None:
                use_alive = kernels.distinct_within_group(
                    gid, alive, rank_key(arg_col), arg_col.valid)
            if arg_col is not None and arg_col.dtype == "str":
                out.append(self._agg_string(spec, arg_col, gid, use_alive,
                                            cap_out))
                continue
            arg = None
            if arg_col is not None:
                data = arg_col.canon().data
                if spec.func == "sum" and (arg_col.dtype == "int"
                                           or is_dec(arg_col.dtype)):
                    data = data.astype(phys_dtype("int"))
                arg = (data, arg_col.valid)
            vals, valid = kernels.agg_apply(gid, use_alive, spec.func, arg,
                                            cap_out)
            if arg_col is not None and is_dec(arg_col.dtype) and \
                    spec.func in ("avg", "stddev_samp"):
                # the kernel averaged SCALED ints; descale to float value
                vals = vals / 10.0 ** dec_scale(arg_col.dtype)
            out.append(DCol(spec.dtype, vals.astype(phys_dtype(spec.dtype)),
                            valid))
        return out

    def _agg_string(self, spec: AggSpec, arg_col: DCol, gid: jax.Array,
                    alive: jax.Array, cap_out: int) -> DCol:
        if spec.func == "count":
            vals, valid = kernels.agg_apply(
                gid, alive, "count", (jnp.zeros_like(arg_col.data),
                                      arg_col.valid), cap_out)
            return DCol("int", vals.astype(phys_dtype("int")), valid)
        if spec.func not in ("min", "max"):
            raise NotImplementedError(f"device {spec.func} over strings")
        from .device import string_rank_maps
        ranks, rank_to_code = string_rank_maps(arg_col.dictionary)
        rank_data = jexprs._lut_gather(arg_col.data, ranks)
        vals, valid = kernels.agg_apply(gid, alive, spec.func,
                                        (rank_data, arg_col.valid), cap_out)
        codes = jexprs._lut_gather(vals.astype(_I32), rank_to_code)
        return DCol("str", codes, valid, arg_col.dictionary)

    # -- joins ---------------------------------------------------------------
    def _run_join(self, node: JoinNode) -> DTable:
        if node.kind == "right":
            return self._right_join(node)
        left = self.execute(node.left)
        right = self.execute(node.right)
        return self._join(node, left, right)

    def _right_join(self, node: JoinNode) -> DTable:
        # right join == left join with sides swapped, columns re-ordered
        residual = node.residual
        nl = len(node.left.out_names)
        nr = len(node.right.out_names)
        if residual is not None:
            # rebase combined-schema column indices [left|right] -> [right|left]
            residual = _shift_residual(residual, nl, nr)
        swapped = dataclasses.replace(
            node, kind="left", left=node.right, right=node.left,
            left_keys=node.right_keys, right_keys=node.left_keys,
            residual=residual,
            out_names=[f"__r{i}" for i in range(len(node.out_names))])
        lt = self.execute(node.left)
        rt = self.execute(node.right)
        out = self._join(swapped, rt, lt)
        cols = out.cols[len(rt.cols):] + out.cols[:len(rt.cols)]
        assert len(cols) == nl + len(rt.cols)
        return DTable(list(node.out_names), cols, out.alive)

    def _join(self, node: JoinNode, left: DTable, right: DTable) -> DTable:
        kind = node.kind
        # Every anti branch below consults null_aware only when residual is
        # None; the combination is planner-rejected (planner.py _decorrelate)
        # — a real raise (assert strips under -O) so a future planner change
        # can't silently keep rows that NOT IN semantics exclude.
        if node.null_aware and node.residual is not None:
            raise NotImplementedError(
                "null-aware anti join with residual is unsupported")
        lcap, rcap = left.capacity, right.capacity
        if kind == "cross":
            lo = jnp.zeros(lcap, _I32)
            perm, rcount_t = kernels.compaction_perm(right.alive)
            cnt = jnp.where(left.alive, rcount_t, 0).astype(_I32)
            out, _, _ = self._expand_combine(node, left, right, lo, cnt, perm,
                                             residual=node.residual)
            return self._maybe_compact(out)

        lkeys = [self._eval(e, left) for e in node.left_keys]
        rkeys = [self._eval(e, right) for e in node.right_keys]
        lvalid = jnp.ones(lcap, bool)
        rvalid = jnp.ones(rcap, bool)
        for c in lkeys:
            lvalid = lvalid & c.valid
        for c in rkeys:
            rvalid = rvalid & c.valid

        if len(lkeys) == 1 and kind in ("inner", "left", "semi", "anti"):
            # direct-address fast path: the NDS star-join shape (single int
            # key, unique build side whose keys span at most 2^24 —
            # dimension surrogate keys, filtered or not). Replaces the
            # sort-based machinery (dense_rank over lcap+rcap rows + build
            # sort + expansion) with one scatter + gathers: TPU lax.sort is
            # O(log^2 n) merge passes over every operand, the dominant HBM
            # traffic of a power-run query program.
            out = self._fast_join(node, left, right, lkeys[0], rkeys[0],
                                  left.alive & lvalid, right.alive & rvalid,
                                  lvalid, rvalid)
            if out is not None:
                return out

        if self._mesh is not None and kind == "inner":
            out = self._mesh_shuffle_join(node, left, right, lkeys, rkeys,
                                          lvalid, rvalid)
            if out is not None:
                return out

        self.sorted_joins += 1
        self.sorted_probe_rows += lcap
        key_data = []
        for lc, rc in zip(lkeys, rkeys):
            ld, rd = _joinable_pair(lc, rc)
            key_data.append(jnp.concatenate([ld, rd]))
        match_alive = jnp.concatenate([left.alive & lvalid,
                                       right.alive & rvalid])
        gid, _ = self._dense_rank(
            key_data, [jnp.ones(lcap + rcap, bool)] * len(key_data),
            match_alive)
        l_gid, r_gid = gid[:lcap], gid[lcap:]

        _, perm_r = kernels.build_side(
            jnp.where(match_alive[lcap:], r_gid, jnp.iinfo(_I32).max),
            right.alive & rvalid)
        lo, cnt = kernels.probe_counts_by_gid(
            r_gid, right.alive & rvalid, l_gid, left.alive & lvalid,
            gid_cap=lcap + rcap)

        if kind in ("semi", "anti") and node.residual is None:
            matched = cnt > 0
            if kind == "semi":
                alive = left.alive & matched
            else:
                if node.null_aware:
                    build_has_null = bool(self._decide_exact(
                        jnp.any(right.alive & ~rvalid)))
                    if build_has_null:
                        alive = jnp.zeros(lcap, bool)
                    else:
                        alive = left.alive & lvalid & ~matched
                else:
                    alive = left.alive & ~matched
            return self._maybe_compact(
                DTable(list(node.out_names), left.cols, alive))

        if kind in ("semi", "anti"):
            # residual semi/anti: expand, evaluate, reduce to a left-row flag
            combined, left_idx, _ = self._expand_combine(
                node, left, right, lo, cnt, perm_r,
                residual=node.residual)
            hit = jax.ops.segment_sum(
                combined.alive.astype(_I32),
                jnp.where(combined.alive, left_idx, lcap),
                num_segments=lcap + 1)[:lcap] > 0
            alive = left.alive & hit if kind == "semi" else left.alive & ~hit
            return self._maybe_compact(
                DTable(list(node.out_names), left.cols, alive))

        inner, left_idx, right_rows = self._expand_combine(
            node, left, right, lo, cnt, perm_r, residual=node.residual)
        if kind == "inner":
            return self._maybe_compact(inner)
        matched_left = jax.ops.segment_sum(
            inner.alive.astype(_I32),
            jnp.where(inner.alive, left_idx, lcap),
            num_segments=lcap + 1)[:lcap] > 0
        unmatched_l = left.alive & ~matched_left
        pieces = [inner, _null_extend(left, right, unmatched_l, side="right",
                                      names=list(node.out_names))]
        if kind == "full":
            matched_right = jnp.zeros(rcap + 1, bool).at[
                jnp.where(inner.alive, right_rows, rcap)].set(True)[:rcap]
            unmatched_r = right.alive & ~matched_right
            pieces.append(_null_extend_left(left, right, unmatched_r,
                                            names=list(node.out_names)))
        return _concat_dtables(pieces, list(node.out_names))

    def _mesh_shuffle_join(self, node: JoinNode, left: DTable, right: DTable,
                           lkeys: list, rkeys: list, lvalid, rvalid
                           ) -> Optional[DTable]:
        """Partitioned shuffle join for fact-fact joins on a mesh: hash-
        repartition BOTH sides by the join key (all_to_all of bounded
        blocks), then join shard-locally — the fact sides never gather
        (Spark shuffle join; SURVEY.md §2 parallelism table last row).
        GSPMD's fallback for the generic sort-based join pulls fact-sized
        buffers to every device. Column/dtype eligibility is static; the
        capacity gate is a RECORDED branch (replay follows the record-time
        choice — capacities drift under streaming inflation), and the max
        hash-block / per-shard match counts are recorded schedule
        decisions."""
        from ...parallel import dist_ops

        mesh = self._mesh
        nsh = mesh.devices.size
        lcap, rcap = left.capacity, right.capacity
        if any(c.parts is not None for c in left.cols + right.cols):
            return None
        pairs = [_joinable_pair(a, b) for a, b in zip(lkeys, rkeys)]
        if not pairs or any(not jnp.issubdtype(a.dtype, jnp.integer)
                            for a, _ in pairs):
            return None
        # capacity gate AFTER the static gates: the recorded branch must sit
        # at a deterministic schedule position, and replay follows the
        # record-time choice (capacities drift under streaming inflation).
        # Only the min-rows threshold is a pure perf choice; divisibility is
        # a STRUCTURAL precondition (shard_rows = cap // nsh truncates rows
        # otherwise), so it is re-verified against the replay-time
        # capacities — drift to a non-divisible cap forces a re-record
        # instead of silently dropping trailing rows.
        if not self._decide_branch(
                min(lcap, rcap) >= max(self._shard_min_rows, nsh)
                and lcap % nsh == 0 and rcap % nsh == 0):
            return None
        if lcap % nsh != 0 or rcap % nsh != 0:
            # ReplayMismatch (not NotJittable): the caller routes it to a
            # fresh record, which re-evaluates the gate against the drifted
            # capacities and takes the generic join — NotJittable would mark
            # the entry permanently eager instead
            raise ReplayMismatch(
                f"shuffle-join capacities ({lcap}, {rcap}) drifted off the "
                f"shard-count multiple ({nsh}); re-record required")
        lkd = [a for a, _ in pairs]
        rkd = [b for _, b in pairs]
        l_ok = left.alive & lvalid
        r_ok = right.alive & rvalid

        def repart(kd, ok, cols):
            cap = int(ok.shape[0])
            shard_rows = cap // nsh
            iota = jnp.arange(cap, dtype=_I32)
            dest = dist_ops._multi_hash(kd, nsh)
            pair_id = jnp.where(ok, (iota // shard_rows) * nsh + dest,
                                nsh * nsh)
            # _seg picks per mode: masked fused reduce under trace (a
            # fact-sized segment_sum scatter would serialize inside every
            # compiled run), O(n) segment_sum on the eager record pass (the
            # masked form would materialize an (nsh^2, n) intermediate).
            # The dead-row sentinel id nsh*nsh falls outside num_segments
            # and drops out on either path.
            sizes = kernels._seg(ok.astype(_I32), pair_id, nsh * nsh, "sum")
            per_pair = bucket(max(self._decide_cap(jnp.max(sizes)), 1))
            fn = dist_ops.repartition_by_key(mesh, per_pair, emit_key=False)
            out_flat, out_alive, _, overflow = fn(list(kd) + list(cols),
                                                  ok, list(kd))
            # per_pair covers the recorded max block; drift re-records
            self._decide_exact(overflow)
            return out_flat[:len(kd)], out_flat[len(kd):], out_alive

        l_flat = [x for c in left.cols for x in (c.data, c.valid)]
        r_flat = [x for c in right.cols for x in (c.data, c.valid)]
        lkd2, l_cols2, l_al2 = repart(lkd, l_ok, l_flat)
        rkd2, r_cols2, r_al2 = repart(rkd, r_ok, r_flat)

        counts, lo, cnt, perm_r = dist_ops.shuffle_join_counts(mesh)(
            tuple(lkd2), l_al2, tuple(rkd2), r_al2)
        cap_out_shard = bucket(max(self._decide_cap(jnp.max(counts)), 1))
        out_l, out_r, out_alive = dist_ops.shuffle_join_expand(
            mesh, cap_out_shard)(lo, cnt, perm_r, l_al2,
                                 tuple(l_cols2), tuple(r_cols2))

        def rebuild(cols_src, flat):
            out = []
            for i, c in enumerate(cols_src):
                out.append(dataclasses.replace(
                    c, data=flat[2 * i],
                    valid=flat[2 * i + 1].astype(bool), parts=None))
            return out
        cols = rebuild(left.cols, list(out_l)) + rebuild(right.cols,
                                                         list(out_r))
        out = DTable(self._combined_names(node, len(cols)), cols, out_alive)
        return self._apply_residual(node.residual, out)

    @staticmethod
    def _combined_names(node: JoinNode, ncols: int) -> list[str]:
        return list(node.out_names) if len(node.out_names) == ncols \
            else [f"__c{i}" for i in range(ncols)]

    def _apply_residual(self, residual, out: DTable) -> DTable:
        if residual is None:
            return out
        mask = jexprs.evaluate(residual, out, subquery_eval=self._ectx())
        return DTable(out.names, out.cols,
                      kernels.filter_alive(out.alive, mask.data, mask.valid))

    def _fast_join(self, node: JoinNode, left: DTable, right: DTable,
                   lkey: DCol, rkey: DCol, l_ok: jax.Array, r_ok: jax.Array,
                   lvalid: jax.Array, rvalid: jax.Array) -> Optional[DTable]:
        """Direct-address single-key join against a unique build side.

        Build: scatter build-row indices into a [LIMIT] table addressed by
        (key - min_key). Probe: ONE gather per probe row, lut[key - min_key],
        under a range test made on the keys themselves (two comparisons, no
        subtraction, so nothing wraps). 1:1 match means the output keeps
        the probe capacity — no expansion step, no row-count decision, no
        sorts.

        LIMIT is the ladder bucket of the SPAN of the live build keys,
        rmax - rmin + 1, not a multiple of the build side's capacity: a
        filter thins a dimension's rows, not the span of its surrogate
        keys, so a dimension compacted to 18 survivors over 18,000 keys
        joins through a 32,768-entry table. The span is data and the
        table's size a shape, so it enters the program as every other
        data-dependent shape does (_decide_table_lazy): the eligibility
        ``unique & cnt_r > 0 & span <= _DIRECT_SPAN_MAX`` is recorded as an
        exact, one-sided decision, and under it the span as a ``cap``,
        which a replay passes iff its traced span is at most the recorded
        bucket. A parameter draw that moves the span inside its bucket
        replays; one that leaves it re-records.

        The build key is NOT gathered back to confirm the match: the
        decisions imply it. Under ``unique & cnt_r > 0`` and ``span <=
        LIMIT`` every live build key lies in [rmin, rmin + LIMIT), none was
        kept out of the table and no two live rows share an address, so
        lut[p] >= 0 iff a live build row holds key rmin + p, and that row
        is lut[p]; for rmin <= ld <= rmax, lut[ld - rmin] >= 0 <=>
        rd[lut[ld - rmin]] == ld. WHAT GUARDS THE MATCH IS THEREFORE THE
        SCHEDULE CHECK, and it guards three things: a replay over a build
        side that left the exact decision (a duplicate key, no live row), or
        whose span outgrew the recorded bucket (the ``cap``: its keys past
        the table are in no entry, and a probe for one reads a neighbour's),
        computes wrong rows, and is right only because it is thrown away.
        Every path that hands out a replay's rows verifies its check
        scalars first and raises ReplayMismatch (a re-record) on drift:
        _verify_schedule in CompiledQuery.run, BatchedQuery._verify in
        BatchedQuery.run, ShardedMorselQuery._verify in shard_exec (every
        replica's scalar). A new replay path must do the same before it
        returns rows.
        """
        kind = node.kind
        lcap, rcap = left.capacity, right.capacity
        ld, rd = _joinable_pair(lkey, rkey)
        if not jnp.issubdtype(rd.dtype, jnp.integer):
            return None    # float keys: no address arithmetic
        if lkey.codebook is not None and rkey.codebook is None:
            # the build keys were mapped into the probe side's code space
            # (_joinable_pair): one the codebook lacks is -1 there and
            # matches no probe row. It stays out of the table — several of
            # them would read as one duplicated key, in the replay alone
            # (the record pass sees the decoded values)
            r_ok = r_ok & (rd >= 0)
        big = jnp.iinfo(rd.dtype).max
        small = jnp.iinfo(rd.dtype).min
        state: dict = {}

        def within(key: jax.Array, width: int) -> jax.Array:
            # key < rmin + width for key >= rmin. rmax - rmin wraps between
            # keys near both ends of the dtype and would read a span that is
            # not one: where rmin + width - 1 leaves the dtype no key can
            # pass it, elsewhere the sum is exact
            rmin = state["rmin"]
            fits = rmin <= big - (width - 1)
            return ~fits | (key <= rmin + (width - 1))

        def measure() -> jax.Array:
            rmin = jnp.min(jnp.where(r_ok, rd, big))
            rmax = jnp.max(jnp.where(r_ok, rd, small))
            cnt_r = jnp.sum(r_ok.astype(_I32))
            state.update(rmin=rmin, rmax=rmax, cnt_r=cnt_r)
            # exact up to the bound, one past it beyond: a check scalar is
            # an i32, and a replay past the bound fails whatever it reads
            span = jnp.where(within(rmax, _DIRECT_SPAN_MAX),
                             rmax - rmin + 1, _DIRECT_SPAN_MAX + 1)
            return jnp.where(cnt_r > 0, span, 0).astype(_I32)

        def probe(limit: int) -> jax.Array:
            # a live key past the table (a replay whose span outgrew the
            # recorded bucket) goes where the dead rows go: it fails the
            # cap check, and must not read as a duplicate on its way
            scatter_idx = jnp.where(r_ok & within(rd, limit),
                                    rd - state["rmin"], limit)
            hist = jnp.zeros(limit + 1, _I32).at[scatter_idx].add(1)[:limit]
            state.update(scatter_idx=scatter_idx)
            return ((jnp.max(hist) <= 1)
                    & (state["cnt_r"] > 0)).astype(_I32)

        limit = self._decide_table_lazy(measure, probe, _DIRECT_SPAN_MAX)
        if not limit:
            return None
        self.direct_joins += 1
        self.direct_probe_rows += lcap
        rmin, rmax = state["rmin"], state["rmax"]
        scatter_idx = state["scatter_idx"]

        lut = jnp.full(limit + 1, -1, _I32).at[scatter_idx].set(
            jnp.arange(rcap, dtype=_I32))[:limit]
        # the range is tested on the keys, never on ld - rmin (which wraps
        # for a probe key at wrap distance); the recorded decisions make
        # the lut entry the whole match (docstring)
        in_range = (ld >= rmin) & (ld <= rmax)
        r_row = lut[jnp.clip(ld - rmin, 0, limit - 1)]
        safe_r = jnp.clip(r_row, 0, rcap - 1)
        matched = l_ok & in_range & (r_row >= 0)

        if kind in ("semi", "anti") and node.residual is None:
            if kind == "semi":
                alive = left.alive & matched
            elif node.null_aware:
                build_has_null = bool(self._decide_exact(
                    jnp.any(right.alive & ~rvalid)))
                alive = jnp.zeros(lcap, bool) if build_has_null \
                    else left.alive & lvalid & ~matched
            else:
                alive = left.alive & ~matched
            return self._maybe_compact(
                DTable(list(node.out_names), left.cols, alive))

        rcols = _gather_cols(right.cols, safe_r)
        names = list(node.out_names) if len(node.out_names) == \
            len(left.cols) + len(rcols) else \
            [f"__c{i}" for i in range(len(left.cols) + len(rcols))]
        combined = DTable(names, list(left.cols) + rcols, left.alive)
        if node.residual is not None:
            mask = jexprs.evaluate(node.residual, combined,
                                   subquery_eval=self._ectx())
            matched = matched & mask.data.astype(bool) & mask.valid

        if kind == "semi":
            return self._maybe_compact(DTable(
                list(node.out_names), left.cols, left.alive & matched))
        if kind == "anti":
            return self._maybe_compact(DTable(
                list(node.out_names), left.cols, left.alive & ~matched))
        if kind == "inner":
            return self._maybe_compact(DTable(
                combined.names, combined.cols, left.alive & matched))
        # left join: 1:1 — unmatched probe rows keep a NULL right side
        # (canonical zeros under ~matched: DCol's null-payload invariant)
        def null_out(c: DCol) -> DCol:
            data = jnp.where(matched, c.data, jnp.zeros((), c.data.dtype))
            return dataclasses.replace(
                c, data=data, valid=c.valid & matched,
                parts=None if c.parts is None else tuple(
                    null_out(p) for p in c.parts))
        out_cols = list(left.cols) + [null_out(c) for c in rcols]
        return DTable(list(node.out_names), out_cols, left.alive)

    def _expand_combine(self, node: JoinNode, left: DTable, right: DTable,
                        lo, cnt, perm_r, residual=None
                        ) -> tuple[DTable, jax.Array, jax.Array]:
        """Materialize matched pairs; returns (combined, left_idx, right_rows)
        — all padded to the planned output capacity, uncompacted."""
        total_t = jnp.sum(cnt)
        total = self._decide_cap(total_t)
        cap_out = bucket(max(total, 1))
        self.expanded_join_rows += cap_out
        left_idx, build_pos, alive_out = kernels.expand_join(
            lo, cnt, left.alive, cap_out)
        right_rows = perm_r[jnp.clip(build_pos, 0, right.capacity - 1)]
        cols = _gather_cols(left.cols, left_idx) + \
            _gather_cols(right.cols, right_rows)
        out = DTable(self._combined_names(node, len(cols)), cols, alive_out)
        out = self._apply_residual(residual, out)
        return out, left_idx, right_rows


# -- plan utilities -----------------------------------------------------------

_MASKED_AGG_FUNCS = frozenset(
    {"count_star", "count", "sum", "min", "max", "avg"})


def _masked_reduction(node: AggregateNode) -> bool:
    """A keyless aggregate that kernels._seg reduces by the alive-masked
    path alone: ONE static group, integer operands (_maybe_compact)."""
    if node.group_exprs or node.rollup or node.rollup_levels is not None:
        return False
    x64 = jax.config.read("jax_enable_x64")
    for s in node.aggs:
        if s.distinct or s.func not in _MASKED_AGG_FUNCS:
            return False
        if s.func == "avg" and not x64:
            return False          # sums in float: the segment path
        if s.arg is not None and (
                s.arg.dtype == "str" or not jnp.issubdtype(
                    phys_dtype(s.arg.dtype), jnp.integer)):
            return False
    return True


def _grouping_sets(node: AggregateNode) -> list:
    """The grouping sets ``node`` emits, each the indices of its group_exprs
    that stay keys: a rollup's prefixes, the full one first (or the levels a
    segmented rollup names), else the one full set."""
    if node.rollup_levels is not None:
        return [list(range(k)) for k in node.rollup_levels]
    if node.rollup:
        return [list(range(k))
                for k in range(len(node.group_exprs), -1, -1)]
    return [list(range(len(node.group_exprs)))]


_PLAN_SHAPE_COUNTERS = (_metrics.WINDOW_NODES, _metrics.ROLLUP_SETS,
                        _metrics.SETOP_NODES, _metrics.OUTER_JOINS,
                        _metrics.STAR_JOINS)


def _plan_shapes(plan) -> tuple:
    """(WindowNodes, grouping sets that rollup AggregateNodes emit,
    SetOpNodes, outer JoinNodes, JoinNodes whose build side is a star's own
    join tree) of one program's plan, or of the member plans of a fused
    group; a node two parents share counts once. Another compile unit's
    nodes stand behind a VirtualScanNode and are its own."""
    windows = sets = setops = outer = stars = 0
    for n in iter_plan_nodes(plan):
        if isinstance(n, WindowNode):
            windows += 1
        elif isinstance(n, AggregateNode) and n.rollup:
            sets += len(_grouping_sets(n))
        elif isinstance(n, SetOpNode):
            setops += 1
        elif isinstance(n, JoinNode) and n.kind in ("left", "right", "full"):
            outer += 1
        elif isinstance(n, JoinNode) and n.star_build:
            stars += 1
    return windows, sets, setops, outer, stars


def _mask_carrying_filters(root: PlanNode) -> frozenset:
    """ids of the FilterNodes under ``root`` (subquery plans included) whose
    EVERY consumer, through ProjectNodes only, is a _masked_reduction: they
    hand on their child's columns under the narrowed alive mask instead of
    compacting (_maybe_compact). execute() memoises by id(node), so a
    filter or a projection two parents share runs once: one parent of
    another kind — the root and an expression count as such — and the
    filter compacts."""
    from ..streaming import _expr_subplans
    consumers: dict[int, list] = {id(root): [None]}
    filters = []
    for n in iter_plan_nodes(root):
        if isinstance(n, MaterializedNode):
            continue
        if isinstance(n, FilterNode):
            filters.append(n)
        for f in ("child", "left", "right"):
            sub = getattr(n, f, None)
            if isinstance(sub, PlanNode):
                consumers.setdefault(id(sub), []).append(n)
        for sub in _expr_subplans(n):      # a BScalarSubquery's plan
            consumers.setdefault(id(sub), []).append(None)

    memo: dict[int, bool] = {}

    def reduced(n: PlanNode) -> bool:
        got = memo.get(id(n))
        if got is None:
            got = memo[id(n)] = all(
                (isinstance(c, AggregateNode) and _masked_reduction(c)) or
                (isinstance(c, ProjectNode) and reduced(c))
                for c in consumers.get(id(n), (None,)))
        return got

    return frozenset(id(f) for f in filters if reduced(f))


def _plan_fingerprint(node, mat_by_identity: bool = True) -> str:
    """Stable structural hash of a plan subtree (for executor-synthesized
    segment keys; CTE segments use planner AST fingerprints instead). Two
    structurally identical subtrees — including literals, so stream-
    parameterized plans never collide — share a segment cache slot.
    MaterializedNodes hash by identity (callers exclude them), or, for a
    program's name, which no ``id()`` may enter, by label and schema."""
    import dataclasses as _dc

    parts: list[str] = []

    def rec(x):
        if isinstance(x, MaterializedNode):
            parts.append(f"mat:{id(x)}" if mat_by_identity else
                         f"mat:{x.label}:{x.out_names}:{x.out_dtypes}")
            return
        if isinstance(x, np.ndarray):
            # repr truncates long arrays -> collision risk; hash content
            parts.append(f"nd{x.dtype}{x.shape}:" + (
                repr(x.tolist()) if x.dtype == object
                else hashlib.sha1(x.tobytes()).hexdigest()))
            return
        if _dc.is_dataclass(x) and not isinstance(x, type):
            parts.append(type(x).__name__ + "(")
            for f in _dc.fields(x):
                parts.append(f.name + "=")
                rec(getattr(x, f.name))
                parts.append(",")
            parts.append(")")
        elif isinstance(x, (list, tuple)):
            parts.append("[")
            for v in x:
                rec(v)
                parts.append(",")
            parts.append("]")
        else:
            parts.append(repr(x))

    rec(node)
    return hashlib.sha1("".join(parts).encode()).hexdigest()[:16]


# -- expression utilities -----------------------------------------------------

def _shift_residual(expr: BExpr, nl: int, nr: int) -> BExpr:
    """Rebase bound column indices from [left|right] to [right|left]."""
    from ..plan import BCall, BCol

    if isinstance(expr, BCol):
        idx = expr.index + nr if expr.index < nl else expr.index - nl
        return dataclasses.replace(expr, index=idx)
    if isinstance(expr, BCall):
        return dataclasses.replace(
            expr, args=[_shift_residual(a, nl, nr) for a in expr.args])
    return expr


# -- column utilities --------------------------------------------------------

def _gather_col(c: DCol, idx: jax.Array) -> DCol:
    parts = None
    if c.parts is not None:
        parts = tuple(dataclasses.replace(p, data=p.data[idx],
                                          valid=p.valid[idx])
                      for p in c.parts)
    return dataclasses.replace(c, data=c.data[idx], valid=c.valid[idx],
                               parts=parts)


def _gather_cols(cols: list, idx: jax.Array) -> list:
    """Gather EVERY column of a table by one index vector — the join /
    sort / late-materialization shape."""
    return [_gather_col(c, idx) for c in cols]


def _joinable_pair(a: DCol, b: DCol) -> tuple[jax.Array, jax.Array]:
    """Comparable device key arrays for a join key pair.

    Encoded execution: when one side carries a dictionary codebook the
    join runs ON CODES — the plain side's values remap into the encoded
    side's code space (device.encode_against: exact code or -1, which
    matches nothing), so the big encoded side keeps its i32 codes through
    dense-rank/build/probe instead of decoding every row. Codes are only
    ever compared against codes of the SAME codebook; equality of codes is
    equality of values by construction, and validity masks carry the null
    semantics exactly as on the plain path."""
    if a.dtype == "str" or b.dtype == "str":
        return jexprs._string_pair_keys(a, b)
    if a.codebook is not None or b.codebook is not None:
        if a.codebook is b.codebook:
            return a.canon().data, b.canon().data
        if a.codebook is not None and b.codebook is None:
            return a.canon().data, encode_against(a.codebook, b)
        if b.codebook is not None and a.codebook is None:
            return encode_against(b.codebook, a), b.canon().data
        a, b = decode_col(a), decode_col(b)   # distinct codebooks
    da, db = a.canon().data, b.canon().data
    if da.dtype != db.dtype:
        ct = jnp.promote_types(da.dtype, db.dtype)
        da, db = da.astype(ct), db.astype(ct)
    return da, db


def _null_extend(left: DTable, right: DTable, left_mask: jax.Array,
                 side: str, names: list[str]) -> DTable:
    """Left rows selected by mask, with the right side all-NULL (outer join)."""
    cols = [dataclasses.replace(c) for c in left.cols]
    for c in right.cols:
        cols.append(dataclasses.replace(
            c, data=jnp.zeros(left.capacity, c.data.dtype),
            valid=jnp.zeros(left.capacity, bool), parts=None))
    return DTable(names, cols, left_mask)


def _null_extend_left(left: DTable, right: DTable, right_mask: jax.Array,
                      names: list[str]) -> DTable:
    """Right rows selected by mask, with the left side all-NULL (full outer)."""
    cols = [dataclasses.replace(
        c, data=jnp.zeros(right.capacity, c.data.dtype),
        valid=jnp.zeros(right.capacity, bool), parts=None)
        for c in left.cols]
    cols += [dataclasses.replace(c) for c in right.cols]
    return DTable(names, cols, right_mask)


def _concat_dtables(pieces: list[DTable], names: list[str]) -> DTable:
    """Row-concatenate device tables (merging string dictionaries on host)."""
    ncols = len(pieces[0].cols)
    out_cols: list[DCol] = []
    for ci in range(ncols):
        cols = [_flatten_for_concat(p.cols[ci]) for p in pieces]
        dtype = cols[0].dtype
        if dtype == "str":
            dictionary, datas = jexprs._merge_branch_strings(cols)
            data = jnp.concatenate(datas)
            out_cols.append(DCol("str", data,
                                 jnp.concatenate([c.valid for c in cols]),
                                 dictionary))
        else:
            pd = cols[0].data.dtype
            data = jnp.concatenate([c.data.astype(pd) for c in cols])
            out_cols.append(DCol(dtype, data,
                                 jnp.concatenate([c.valid for c in cols])))
    alive = jnp.concatenate([p.alive for p in pieces])
    return DTable(names, out_cols, alive)


def _flatten_for_concat(c: DCol) -> DCol:
    # pieces may mix encodings (an encoded inner-join piece concatenated
    # with a plain null-extension): codes from different codebooks must
    # never share a buffer, so concatenation is a decode site
    c = decode_col(c)
    if c.parts is None:
        return c
    from .device import _flatten_compound
    return _flatten_compound(c)
