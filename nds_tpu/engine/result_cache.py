"""Semantic result cache: cross-client reuse, subsumption proofs, IVM.

PR 10's service deduplicates only parameter-IDENTICAL in-flight tickets
(the locked result cell); every repeat dashboard load still replans and
re-executes. This module adds the next three reuse tiers, each opt-in and
each bit-identical to recompute by construction:

- **Exact tier** — a capacity-bounded LRU of finished results keyed by
  parameterized-plan fingerprint (``executor.shared_fingerprint`` /
  ``executor._plan_fingerprint``) + parameter vector + backend. The
  service consults it at ADMISSION through a text alias map, so a repeat
  dashboard load touches neither a planner thread nor the device lane.
  Entries are invalidated by the per-table catalog generations of the
  base tables the plan scans (``Session.table_generation`` — registering
  table A never evicts results over table B) and an optional TTL.
- **Subsumption tier** — when a new ticket's plan differs from a cached
  entry only by a provably-narrower filter/date-window over the SAME
  group keys, the answer is computed by re-filtering the cached coarser
  aggregate on host: no scan, no upload. The proof machinery is the PR 4
  verifier's structural fingerprint (``verify.plan_fingerprint``): two
  texts of one template parameterize to the same plan, so containment
  reduces to per-slot value comparisons over comparison conjuncts whose
  column side is structurally one of the aggregate's group keys that
  survives to the output. Any failure of the proof falls back to normal
  execution.
- **Incremental view maintenance** — entries for decomposable aggregates
  store the mergeable partial state ``streaming._decompose`` /
  ``_final_builder`` already define. ``Session._insert``/``_delete``
  publish per-table row deltas after each LF_*/DF_* statement commits,
  and ``apply_delta`` UPDATES the partials (merge inserted-row partials
  through the partial-schema-preserving combine plan; recompute only the
  delta-touched groups for deletes) instead of invalidating — dashboards
  stay warm across maintenance rounds. Bit-identity discipline: only
  partials whose merged columns are order-insensitive (int/date/scaled-
  decimal sums, min/max, counts) are IVM-eligible; float sums (f64
  decimal mode) fall back to invalidation, because re-associated float
  addition cannot promise the recompute hash.

Every tier counts through the metrics registry (``result_cache_*``) and
records flight events, so cache behavior is observable in the same
artifacts as the rest of the service.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, replace
from typing import Optional

import pyarrow as pa

from ..obs import metrics as _metrics
from ..obs.flight import FLIGHT
from . import plan as P
from . import streaming
from .column import is_dec
from .executor import Executor
from .verify import plan_fingerprint


@dataclass
class ResultCacheConfig:
    """Knobs of one ResultCache (mirrored on EngineConfig for property-
    file parity; ServiceConfig.result_cache takes this object directly)."""
    #: cached entries before LRU eviction
    entries: int = 256
    #: seconds before an entry expires (0 = no TTL)
    ttl_s: float = 0.0
    #: prove narrower filters against cached coarser aggregates
    subsumption: bool = False
    #: keep mergeable partial state and absorb LF_*/DF_* deltas
    ivm: bool = False
    #: cached entries of one template tried per subsumption lookup
    subsumption_candidates: int = 8

    @classmethod
    def from_engine(cls, cfg) -> "ResultCacheConfig":
        return cls(entries=cfg.result_cache_entries,
                   ttl_s=cfg.result_cache_ttl_s,
                   subsumption=cfg.result_cache_subsumption,
                   ivm=cfg.result_cache_ivm)


@dataclass
class CacheHit:
    """One answered lookup: the materialized result + which tier served."""
    table: object            # engine.column.Table (read-only, shared)
    kind: str                # "exact" | "subsumed"


# ---------------------------------------------------------------------------
# template analysis (per parameterized-plan fingerprint, memoized)
# ---------------------------------------------------------------------------

@dataclass
class _Slot:
    """One subsumable parameter slot: a comparison conjunct whose column
    side is group key `out_col` of the final output."""
    kind: str                # "lower" (ge/gt) | "upper" (le/lt) | "point"
    op: str                  # canonicalized op with the column on the left
    out_col: int             # final-output column position of the group key
    col_dtype: str
    param_dtype: str


@dataclass
class _InSet:
    """One subsumable IN-list conjunct with hoisted parameter slots."""
    slots: tuple             # parameter slot indices inside the list
    literals: tuple          # non-hoisted list values
    out_col: int
    col_dtype: str


class _TemplateInfo:
    """Structure-only facts about one template (same for every parameter
    vector): subsumption slot map, the cross-length subsumption FAMILY
    key (recognized IN-list extras and parameter indices normalized, so
    ``IN (a, b, c)`` and ``IN (a, b)`` land in one family), and IVM
    eligibility."""
    __slots__ = ("subsumable", "slots", "insets", "family_key", "ivm_ok")

    def __init__(self):
        self.subsumable = False
        self.slots: dict[int, _Slot] = {}
        self.insets: list[_InSet] = []
        self.family_key: Optional[str] = None
        self.ivm_ok = False

    def reduce(self, pvalues: tuple):
        """Split one parameter vector into (non-inset values in slot
        order, per-inset value frozensets, non-inset slot order). Two
        plans of one family align POSITIONALLY on the reduced vector —
        outside the recognized IN lists their structures are identical,
        and parameterize_plan numbers slots in traversal order."""
        inset_idx = {i for s in self.insets for i in s.slots}
        order = [i for i in range(len(pvalues)) if i not in inset_idx]
        reduced = tuple(pvalues[i] for i in order)
        sets = tuple(frozenset(s.literals)
                     | {pvalues[j] for j in s.slots} for s in self.insets)
        return reduced, sets, order


def _conjuncts(e):
    if isinstance(e, P.BCall) and e.op == "and":
        for a in e.args:
            yield from _conjuncts(a)
    else:
        yield e


def _has_params(x) -> bool:
    stack = [x]
    while stack:
        v = stack.pop()
        if isinstance(v, P.BParam):
            return True
        if isinstance(v, P.BCall):
            stack.extend(v.args)
            if isinstance(v.extra, list):
                stack.extend(v.extra)
        elif isinstance(v, P.BScalarSubquery):
            return True       # conservatively opaque: subplan literals
    return False


def _param_counts(pplan) -> dict[int, int]:
    """How many places each parameter slot appears in — a slot consumed
    anywhere beyond its one recognized conjunct is opaque (re-filtering
    the output would not reproduce its other effect)."""
    counts: dict[int, int] = {}
    seen: set[int] = set()
    stack: list = [pplan]
    while stack:
        x = stack.pop()
        if isinstance(x, P.BParam):
            counts[x.index] = counts.get(x.index, 0) + 1
            continue
        if x is None or isinstance(x, (str, int, float, bool)):
            continue
        if dataclasses.is_dataclass(x) and not isinstance(x, type):
            if id(x) in seen:
                continue
            seen.add(id(x))
            if isinstance(x, P.MaterializedNode):
                continue
            for name in P.type_fields(x):
                stack.append(getattr(x, name))
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
    return counts


def _parent_counts(root) -> dict[int, int]:
    counts: dict[int, int] = {}
    for n in P.iter_plan_nodes(root):
        for f in ("child", "left", "right"):
            sub = getattr(n, f, None)
            if isinstance(sub, P.PlanNode):
                counts[id(sub)] = counts.get(id(sub), 0) + 1
        for sub in streaming._expr_subplans(n):
            counts[id(sub)] = counts.get(id(sub), 0) + 1
    return counts


def _subst_cols(e, exprs):
    """Push an expression through a ProjectNode: every BCol reference is
    replaced by the projection's defining expression (composition), so a
    group key keeps one structural identity all the way down to the
    filter's schema."""
    if isinstance(e, P.BCol):
        return exprs[e.index]
    if isinstance(e, P.BCall):
        return replace(e, args=[_subst_cols(a, exprs) for a in e.args])
    return e


_FLIP = {"ge": "le", "gt": "lt", "le": "ge", "lt": "gt", "eq": "eq"}


def _order_safe_partials(recipes, p_dtypes) -> bool:
    """May these partials be re-associated (merged with a delta, or kept
    while sibling groups recompute) and still hash-match a cold
    recompute? min/max and exact-integer sums are order-insensitive;
    float sums are not (f64 addition does not re-associate bit-stably)."""
    for kind, idxs in recipes:
        if kind in ("min", "max"):
            continue
        for j in idxs:
            d = p_dtypes[j]
            if not (d in ("int", "date") or is_dec(d)):
                return False
    return True


def _analyze_template(pplan) -> _TemplateInfo:
    """Structure-only analysis of one parameterized plan: which parameter
    slots are subsumable (comparison conjuncts over output-surviving
    group keys) and whether the shape supports IVM partial state."""
    info = _TemplateInfo()
    path, agg = streaming._path_to_aggregate(pplan)
    if agg is None:
        return info
    mergeable = streaming._mergeable(agg)
    if mergeable and not agg.rollup:
        try:
            _specs, recipes, _pn, p_dtypes = streaming._decompose(agg)
        except Exception:
            recipes = None
        if recipes is not None and _order_safe_partials(recipes, p_dtypes):
            info.ivm_ok = True
    if not mergeable:
        return info
    # subsumption shape: only order/projection above the aggregate (a
    # LIMIT would have truncated groups the narrower query still needs; a
    # HAVING/window above could consume the differing parameters)
    if any(not isinstance(n, (P.SortNode, P.ProjectNode)) for n in path):
        return info
    # where does each group key land in the FINAL output?
    pos = {i: i for i in range(len(agg.group_exprs))}
    for node in reversed(path):          # nearest-to-aggregate first
        if isinstance(node, P.SortNode):
            continue
        new_pos: dict[int, int] = {}
        inv = {p: g for g, p in pos.items()}
        for j, e in enumerate(node.exprs):
            if isinstance(e, P.BCol) and e.index in inv:
                new_pos[inv[e.index]] = j
        pos = new_pos
    if not pos:
        return info
    # the filter chain under the aggregate must be exclusively owned by
    # it: a shared (CTE) subtree narrowed here would also narrow some
    # other consumer the re-filter cannot see
    parents = _parent_counts(pplan)
    counts = _param_counts(pplan)
    node = agg.child
    cur = list(agg.group_exprs)          # group exprs in `node`'s schema
    memo: dict[int, int] = {}
    recognized: set[int] = set()         # ids of recognized inset BCalls
    while True:
        if parents.get(id(node), 0) > 1:
            return info
        if isinstance(node, P.FilterNode):
            fps = [plan_fingerprint(e, memo) for e in cur]
            for conj in _conjuncts(node.predicate):
                _classify_conjunct(conj, fps, pos, counts, info, memo,
                                   recognized)
            node = node.child
        elif isinstance(node, P.ProjectNode):
            cur = [_subst_cols(e, node.exprs) for e in cur]
            node = node.child
        else:
            break
    info.subsumable = bool(info.slots or info.insets)
    if info.subsumable:
        info.family_key = _family_fingerprint(pplan, recognized)
    return info


def _family_fingerprint(pplan, recognized: set[int]) -> str:
    """The cross-length subsumption family: fingerprint of the plan with
    every parameter index normalized and every RECOGNIZED group-key
    IN-list's member list collapsed to one token. Templates differing
    only in how many values those IN lists carry then share one family,
    while any other structural difference (including the lengths of
    UNrecognized IN lists) keeps them apart — positional slot pairing
    inside a family stays sound."""
    from .jax_backend.executor import _plan_fingerprint

    memo: dict[int, object] = {}

    def rw(x):
        if isinstance(x, P.BParam):
            return replace(x, index=-1)
        if isinstance(x, P.BCall):
            args = [rw(a) for a in x.args]
            if id(x) in recognized:
                extra = "<inset>"
            elif isinstance(x.extra, list):
                extra = [rw(v) if isinstance(v, P.BParam) else v
                         for v in x.extra]
            else:
                extra = x.extra
            return replace(x, args=args, extra=extra)
        if isinstance(x, P.MaterializedNode):
            return x
        if dataclasses.is_dataclass(x) and not isinstance(x, type):
            got = memo.get(id(x))
            if got is not None:
                return got
            out = replace(x, **{f: rw(getattr(x, f))
                                for f in P.type_fields(x)})
            memo[id(x)] = out
            return out
        if isinstance(x, list):
            return [rw(v) for v in x]
        if isinstance(x, tuple):
            return tuple(rw(v) for v in x)
        return x

    return _plan_fingerprint(rw(pplan))


def _classify_conjunct(conj, group_fps, pos, counts, info, memo,
                       recognized: set) -> None:
    if not isinstance(conj, P.BCall):
        return
    if conj.op in ("ge", "gt", "le", "lt", "eq") and len(conj.args) == 2:
        a, b = conj.args
        if isinstance(b, P.BParam) and not _has_params(a):
            col, prm, op = a, b, conj.op
        elif isinstance(a, P.BParam) and not _has_params(b):
            col, prm, op = b, a, _FLIP[conj.op]
        else:
            return
        if counts.get(prm.index, 0) != 1:
            return               # slot consumed elsewhere too: opaque
        g = _group_of(col, group_fps, memo)
        if g is None or g not in pos:
            return
        kind = ("lower" if op in ("ge", "gt")
                else "upper" if op in ("le", "lt") else "point")
        info.slots[prm.index] = _Slot(kind, op, pos[g], col.dtype,
                                      prm.dtype)
    elif conj.op == "in_list" and len(conj.args) == 1 \
            and isinstance(conj.extra, list):
        col = conj.args[0]
        if _has_params(col):
            return
        pslots = tuple(v.index for v in conj.extra
                       if isinstance(v, P.BParam))
        if not pslots or any(counts.get(i, 0) != 1 for i in pslots):
            return
        g = _group_of(col, group_fps, memo)
        if g is None or g not in pos:
            return
        inset = _InSet(pslots,
                       tuple(v for v in conj.extra
                             if not isinstance(v, P.BParam)),
                       pos[g], col.dtype)
        info.insets.append(inset)
        recognized.add(id(conj))


def _group_of(col_expr, group_fps, memo) -> Optional[int]:
    fp = plan_fingerprint(col_expr, memo)
    for g, gfp in enumerate(group_fps):
        if gfp == fp:
            return g
    return None


def _prove_containment(new_info: _TemplateInfo, new_pv: tuple,
                       cand_info: _TemplateInfo,
                       cand_pv: tuple) -> Optional[list]:
    """The containment proof, positional across one family: every
    differing non-inset slot must sit in a recognized comparison AND move
    in the narrowing direction; every recognized IN set must be a subset
    of the cached one. Returns the re-filter predicate pieces
    [(slot_or_inset, value(s))], or None when the new plan is not
    provably contained in the cached entry's."""
    n_red, n_sets, n_order = new_info.reduce(new_pv)
    c_red, c_sets, _c_order = cand_info.reduce(cand_pv)
    if len(n_red) != len(c_red) or len(n_sets) != len(c_sets):
        return None
    preds: list = []
    for pos, (nv, cv) in enumerate(zip(n_red, c_red)):
        if nv == cv:
            continue
        slot = new_info.slots.get(n_order[pos])
        if slot is None:
            return None              # opaque slot differs: no proof
        if slot.kind == "point":
            return None              # different equality: disjoint groups
        try:
            if slot.kind == "lower" and not nv >= cv:
                return None
            if slot.kind == "upper" and not nv <= cv:
                return None
        except TypeError:
            return None
        preds.append((slot, nv))
    for k, (ns, cs) in enumerate(zip(n_sets, c_sets)):
        if ns == cs:
            continue
        if not ns <= cs:
            return None              # widened membership: not contained
        preds.append((new_info.insets[k], sorted(ns)))
    return preds if preds else None


def _refilter(entry: "_Entry", preds: list):
    """Answer the narrower query from the cached coarser aggregate: apply
    the NEW parameter values' conjuncts to the cached FINAL rows on the
    group-key output columns. Each surviving group's aggregate was
    computed from exactly the rows the narrower plan would have seen
    (the filter is a pure function of the group key), so the result is
    bit-identical to recompute; filtering preserves the sort order."""
    names, dtypes = list(entry.out_names), list(entry.out_dtypes)
    pred = None
    for spec, val in preds:
        if isinstance(spec, _Slot):
            c = P.BCall("bool", spec.op,
                        [P.BCol(spec.col_dtype, spec.out_col,
                                names[spec.out_col]),
                         P.BLit(spec.param_dtype, val)])
        else:
            c = P.BCall("bool", "in_list",
                        [P.BCol(spec.col_dtype, spec.out_col,
                                names[spec.out_col])],
                        extra=list(val))
        pred = c if pred is None else P.BCall("bool", "and", [pred, c])
    mat = P.MaterializedNode(table=entry.result, label="result-cache",
                             out_names=names, out_dtypes=dtypes)
    filt = P.FilterNode(mat, pred, out_names=names, out_dtypes=dtypes)
    return Executor(_no_load).execute(filt)


def _no_load(*_a, **_k):
    raise RuntimeError("result-cache plans never scan tables")


# ---------------------------------------------------------------------------
# IVM state: mergeable partials + per-table probe-side scans
# ---------------------------------------------------------------------------

class _IvmState:
    """Everything needed to absorb a table delta into one entry: the
    aggregate's decomposition, its partial table, and — per base table —
    the unique probe-side scan a delta substitutes into."""
    __slots__ = ("agg", "path", "recipes", "p_names", "p_dtypes",
                 "partial_specs", "partial", "partial_plan",
                 "scan_by_table")

    def __init__(self, agg, path, partial_specs, recipes, p_names,
                 p_dtypes, partial, partial_plan, scan_by_table):
        self.agg = agg
        self.path = path
        self.partial_specs = partial_specs
        self.recipes = recipes
        self.p_names = p_names
        self.p_dtypes = p_dtypes
        self.partial = partial
        self.partial_plan = partial_plan
        self.scan_by_table = scan_by_table


def _probe_scan(subtree, table: str):
    """The unique scan of `table` on the probe spine of `subtree`, or
    None. Linearity requirement for delta merging: the aggregate must
    distribute over a row-union of this table — true when its single
    scan flows through filters/projections and the LEFT side of
    inner/left/semi/anti joins (a build-side delta changes every probe
    row's matches instead)."""
    scans = [n for n in P.iter_plan_nodes(subtree)
             if isinstance(n, P.ScanNode) and n.table == table]
    if len(scans) != 1:
        return None
    target = scans[0]

    def on_spine(node) -> bool:
        if node is target:
            return True
        if isinstance(node, (P.FilterNode, P.ProjectNode)):
            return on_spine(node.child)
        if isinstance(node, P.JoinNode) and node.kind in (
                "inner", "left", "semi", "anti"):
            if any(n is target for n in P.iter_plan_nodes(node.right)):
                return False
            return on_spine(node.left)
        return False

    return target if on_spine(subtree) else None


def _execute_plan(session, plan, use_jax: bool):
    """One-shot plan execution through the session's backend (key=None:
    the eager record path — nothing lands in the program caches)."""
    if use_jax:
        from .jax_backend import to_host
        with session._sql_lock:
            jexec = session._jax_executor()
            return to_host(jexec.run_query(None, lambda: plan))
    return Executor(session.load_table).execute(plan)


def _col_values(col):
    """(sorted unique non-null python values, has_null) of one engine
    column — the touched-group key sets a delete recompute filters by."""
    import numpy as np

    valid = np.asarray(col.validity, dtype=bool)
    has_null = bool((~valid).any())
    if col.dtype == "str":
        dec = col.decode()
        vals = sorted({dec[i] for i in np.flatnonzero(valid)})
    else:
        data = np.asarray(col.data)[valid]
        vals = sorted({v.item() for v in np.unique(data)})
    return vals, has_null


def plan_for_cache(session, sql: str, backend: Optional[str] = None):
    """Parse/plan/parameterize one text the way the service's planner
    stage does — shared so direct ResultCache.run callers and tests key
    identically to service tickets."""
    from ..sql import parse_sql
    from .planner import Planner

    cfg = session.config
    use_jax = (backend == "jax") if backend else cfg.use_jax
    plan = Planner(session._catalog()).plan_query(parse_sql(sql))
    streams = False
    if use_jax and cfg.out_of_core:
        jobs = streaming.find_streaming_jobs(
            plan, lambda t: session._est_rows.get(t, 0),
            cfg.out_of_core_min_rows)
        streams = bool(jobs)
    fp = None
    pvalues: tuple = ()
    if use_jax and not streams and cfg.jit_plans and not cfg.mesh_shape:
        from .jax_backend.executor import shared_fingerprint
        pplan, pvals, pdts = P.parameterize_plan(plan)
        if pdts:
            fp = shared_fingerprint(pplan, cfg.shard_min_rows)
            pvalues = tuple(pvals)
    return plan, fp, pvalues, use_jax


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

class _Entry:
    __slots__ = ("key", "template_key", "family", "pvalues", "backend",
                 "result", "out_names", "out_dtypes", "tables", "gens",
                 "snaps", "stored_at", "plan", "ivm", "hits")

    def __init__(self, key, template_key, family, pvalues, backend,
                 result, out_names, out_dtypes, tables, gens, snaps,
                 stored_at, plan, ivm):
        self.hits = 0          # lookups served (system.result_cache)
        self.key = key
        self.template_key = template_key
        self.family = family
        self.pvalues = pvalues
        self.backend = backend
        self.result = result
        self.out_names = out_names
        self.out_dtypes = out_dtypes
        self.tables = tables
        self.gens = gens
        # per-table warehouse MANIFEST versions at store time (snapshot-
        # pinned registrations only; {} when unpinned): the provable
        # snapshot identity — a reader pinned to a different warehouse
        # version never gets this entry, even within one session
        self.snaps = snaps
        self.stored_at = stored_at
        self.plan = plan
        self.ivm = ivm


class ResultCache:
    """The semantic result cache over one Session (cross-client: every
    service client shares it). Thread-safe; the internal lock is never
    held across plan execution, so lookups stay cheap beside IVM work."""

    def __init__(self, session, config: Optional[ResultCacheConfig] = None):
        self.session = session
        self.config = config or ResultCacheConfig()
        self._lock = threading.RLock()
        self._entries: "OrderedDict" = OrderedDict()   # key -> _Entry (LRU)
        self._aliases: dict[tuple, tuple] = {}   # (sql, backend) -> key
        self._by_family: dict = {}        # subsumption family -> [key]
        self._templates: dict = {}        # template_key -> _TemplateInfo

    # -- keying --------------------------------------------------------------
    def _template_key(self, plan, fp, pvalues):
        """(template key, full parameter vector). fp=None plans (streamed
        / jit-off) key on the executor's sha1 structural fingerprint of
        the parameterized plan, with the parameter vector recomputed —
        two texts of one template must not collide on an empty vector."""
        if fp is not None:
            return fp, tuple(pvalues)
        from .jax_backend.executor import _plan_fingerprint
        pplan, pvals, _pdts = P.parameterize_plan(plan)
        return ("pfp", _plan_fingerprint(pplan)), tuple(pvals)

    def _template_info(self, template_key, plan) -> _TemplateInfo:
        with self._lock:
            info = self._templates.get(template_key)
        if info is not None:
            return info
        pplan, _v, _d = P.parameterize_plan(plan)
        info = _analyze_template(pplan)
        with self._lock:
            self._templates.setdefault(template_key, info)
            while len(self._templates) > 4 * max(self.config.entries, 1):
                self._templates.pop(next(iter(self._templates)))
        return info

    @staticmethod
    def _backend_tag(use_jax: bool) -> str:
        return "jax" if use_jax else "numpy"

    # -- validity ------------------------------------------------------------
    def _valid(self, entry: _Entry) -> bool:
        ttl = self.config.ttl_s
        if ttl > 0 and time.time() - entry.stored_at > ttl:
            return False
        gen = self.session.table_generation
        if not all(gen(t) == g for t, g in entry.gens.items()):
            return False
        # snapshot-stamped entries additionally require the READER's
        # pinned warehouse versions to match the entry's: the cached
        # result is served only to the exact snapshot it came from
        snap = self.session.table_snapshot_version
        return all(snap(t) == s for t, s in entry.snaps.items())

    def _drop_locked(self, key, reason: str) -> None:
        entry = self._entries.pop(key, None)
        if entry is None:
            return
        _metrics.RESULT_CACHE_INVALIDATIONS.inc()
        FLIGHT.record("cache_invalidate", reason=reason,
                      template=str(entry.template_key)[:12])

    def _check_locked(self, key) -> Optional[_Entry]:
        """Entry for `key` if currently valid; stale entries that IVM can
        still absorb are KEPT (a maintenance delta is about to re-stamp
        them), everything else stale is dropped + counted."""
        entry = self._entries.get(key)
        if entry is None:
            return None
        if self._valid(entry):
            self._entries.move_to_end(key)
            return entry
        if not (self.config.ivm and entry.ivm is not None):
            self._drop_locked(key, "stale")
        return None

    # -- lookups -------------------------------------------------------------
    def lookup_text(self, sql: str,
                    backend: Optional[str] = None) -> Optional[CacheHit]:
        """Admission-time probe: a text seen before maps straight to its
        entry — no parsing, no planning, no device. Misses are silent
        (the plan-level lookup gives the final verdict). The alias is
        backend-scoped: a numpy-oracle result never serves a jax query."""
        use_jax = (backend == "jax") if backend \
            else self.session.config.use_jax
        alias = (sql, self._backend_tag(use_jax))
        with self._lock:
            key = self._aliases.get(alias)
            if key is None:
                return None
            entry = self._check_locked(key)
            if entry is None:
                if key not in self._entries:
                    del self._aliases[alias]
                return None
            entry.hits += 1
        _metrics.RESULT_CACHE_HITS.inc()
        FLIGHT.record("cache_hit", tier="exact", via="text")
        return CacheHit(entry.result, "exact")

    def lookup_plan(self, sql: str, plan, fp, pvalues,
                    use_jax: bool = True) -> Optional[CacheHit]:
        """Plan-level probe: exact by (template, parameters, backend),
        then the subsumption proof against cached siblings of the same
        template. Counts the definitive hit/miss."""
        tag = self._backend_tag(use_jax)
        tk, pv = self._template_key(plan, fp, pvalues)
        key = (tk, pv, tag)
        with self._lock:
            entry = self._check_locked(key)
            if entry is not None:
                self._aliases[(sql, tag)] = key
                entry.hits += 1
        if entry is not None:
            _metrics.RESULT_CACHE_HITS.inc()
            FLIGHT.record("cache_hit", tier="exact", via="plan")
            return CacheHit(entry.result, "exact")
        if self.config.subsumption:
            hit = self._try_subsume(sql, plan, tk, pv, tag, key)
            if hit is not None:
                return hit
        _metrics.RESULT_CACHE_MISSES.inc()
        return None

    def _try_subsume(self, sql, plan, tk, pv, tag,
                     key) -> Optional[CacheHit]:
        info = self._template_info(tk, plan)
        if not info.subsumable or info.family_key is None:
            return None
        with self._lock:
            keys = self._by_family.get(info.family_key, [])
            keys[:] = [k for k in keys if k in self._entries]
            cands = []
            for k in reversed(keys):
                entry = self._entries.get(k)
                if entry is None or entry.backend != tag:
                    continue
                if not self._valid(entry):
                    continue
                cands.append(entry)
                if len(cands) >= self.config.subsumption_candidates:
                    break
        for cand in cands:
            cand_info = self._cand_info(cand)
            if cand_info is None:
                continue
            preds = _prove_containment(info, pv, cand_info, cand.pvalues)
            if preds is None:
                continue
            cand.hits += 1
            table = _refilter(cand, preds)
            _metrics.RESULT_CACHE_SUBSUMPTION_HITS.inc()
            FLIGHT.record("cache_hit", tier="subsumed",
                          from_rows=cand.result.num_rows,
                          to_rows=table.num_rows)
            # the narrowed answer becomes its own exact entry (repeat
            # narrow loads skip the proof); it inherits the parent's
            # generation stamps and data age
            derived = _Entry(key, tk, info.family_key, pv, tag, table,
                             list(cand.out_names), list(cand.out_dtypes),
                             cand.tables, dict(cand.gens),
                             dict(cand.snaps), cand.stored_at, None, None)
            self._insert_entry(sql, derived)
            return CacheHit(table, "subsumed")
        return None

    def _cand_info(self, cand: _Entry) -> Optional[_TemplateInfo]:
        """A candidate's own analysis (its slot ORDER can differ from the
        probe's when IN-list lengths differ): memoized by template key;
        derived entries (plan=None) rely on the memo their creation
        populated."""
        with self._lock:
            got = self._templates.get(cand.template_key)
        if got is not None:
            return got
        if cand.plan is None:
            return None
        return self._template_info(cand.template_key, cand.plan)

    # -- store ---------------------------------------------------------------
    def store(self, sql: str, plan, fp, pvalues, result,
              use_jax: bool = True, gens: Optional[dict] = None) -> None:
        """Cache one finished execution. `gens` should be the per-table
        generation snapshot taken at DISPATCH time (a registration racing
        the store then correctly invalidates the entry); defaults to
        now. Failures degrade to not-caching, never to failing the query."""
        try:
            self._store(sql, plan, fp, pvalues, result, use_jax, gens)
        except Exception as e:   # caching is an optimization, never fatal
            FLIGHT.record("cache_store", status="failed",
                          error=type(e).__name__)

    def _store(self, sql, plan, fp, pvalues, result, use_jax, gens):
        session = self.session
        tables = sorted({n.table for n in P.iter_plan_nodes(plan)
                         if isinstance(n, P.ScanNode)})
        if any(t not in session._schemas for t in tables):
            return
        if any(isinstance(n, P.MaterializedNode) and n.table is not None
               for n in P.iter_plan_nodes(plan)):
            return               # payload tables have no generation identity
        tag = self._backend_tag(use_jax)
        tk, pv = self._template_key(plan, fp, pvalues)
        key = (tk, pv, tag)
        if gens is None:
            gens = {t: session.table_generation(t) for t in tables}
        # any registration between dispatch and store moved the gens (a
        # snapshot change always re-registers), so capturing snaps here
        # is race-free: a mismatch coincides with a gens mismatch that
        # already invalidates the entry
        snaps = {}
        for t in tables:
            sv = session.table_snapshot_version(t)
            if sv is not None:
                snaps[t] = sv
        ivm = None
        family = None
        info = self._template_info(tk, plan)
        if self.config.ivm and info.ivm_ok:
            ivm = self._capture_ivm(plan)
        if self.config.subsumption:
            family = info.family_key
        entry = _Entry(key, tk, family, pv, tag, result,
                       list(plan.out_names), list(plan.out_dtypes),
                       tables, gens, snaps, time.time(), plan, ivm)
        self._insert_entry(sql, entry)
        FLIGHT.record("cache_store", template=str(tk)[:12],
                      tables=",".join(tables), ivm=ivm is not None)

    def snapshot_gens(self, plan) -> dict:
        """Per-table generation snapshot for a later deferred store()."""
        gen = self.session.table_generation
        return {n.table: gen(n.table) for n in P.iter_plan_nodes(plan)
                if isinstance(n, P.ScanNode)}

    def export_snapshot(self) -> list:
        """Exact-tier export for CROSS-PROCESS sharing (the front door's
        ``cache_snapshot`` op): one dict per currently-valid entry that
        has a text alias — a client process keys its local cache on SQL
        text, having no planner of its own. Each item carries the full
        consistency identity beside the result: per-table catalog
        generations and warehouse snapshot versions exactly as stored,
        so the client can re-validate per lookup (the ``cache_validate``
        handshake) before trusting a warmed entry. Cut under the cache
        lock; results are the shared read-only Tables."""
        with self._lock:
            out = []
            seen = set()
            for (sql, tag), key in self._aliases.items():
                entry = self._entries.get(key)
                if entry is None or entry.result is None \
                        or not self._valid(entry) or key in seen:
                    continue
                seen.add(key)
                out.append({"sql": sql, "backend": tag,
                            "gens": dict(entry.gens),
                            "snaps": dict(entry.snaps),
                            "result": entry.result})
            return out

    def validate_stamps(self, gens: dict, snaps: dict) -> bool:
        """The invalidation handshake's server side: do these per-table
        generation/snapshot stamps still match the live session? Exactly
        the ``_valid`` test minus TTL — a client-held entry whose base
        table re-registered or whose warehouse snapshot moved answers
        False (the client must drop it), so N front-end processes can
        never serve a result the engine already invalidated."""
        gen = self.session.table_generation
        if not all(gen(t) == g for t, g in (gens or {}).items()):
            return False
        snap = self.session.table_snapshot_version
        return all(snap(t) == s for t, s in (snaps or {}).items())

    def snapshot_rows(self) -> list:
        """``system.result_cache`` rows: one per live entry, cut under
        the cache lock (entry id is a short stable digest of the full
        key — operators correlate rows across polls, not decode keys)."""
        import hashlib
        with self._lock:
            out = []
            for key, e in self._entries.items():
                digest = hashlib.sha1(repr(key).encode()).hexdigest()[:12]
                out.append({
                    "entry": digest,
                    "template": str(e.template_key)[:16],
                    "backend": e.backend,
                    "rows": e.result.num_rows
                    if e.result is not None else None,
                    "hits": e.hits,
                    "stored_at": round(e.stored_at, 3),
                    "tables": ",".join(e.tables) or None,
                    "ivm": e.ivm is not None})
            return out

    def _insert_entry(self, sql: str, entry: _Entry) -> None:
        with self._lock:
            self._entries[entry.key] = entry
            self._entries.move_to_end(entry.key)
            self._aliases[(sql, entry.backend)] = entry.key
            if entry.family is not None:
                bucket = self._by_family.setdefault(entry.family, [])
                if entry.key not in bucket:
                    bucket.append(entry.key)
            while len(self._entries) > max(1, self.config.entries):
                old_key, old = self._entries.popitem(last=False)
                ob = self._by_family.get(old.family) \
                    if old.family is not None else None
                if ob and old_key in ob:
                    ob.remove(old_key)
            if len(self._aliases) > 8 * max(1, self.config.entries):
                self._aliases = {s: k for s, k in self._aliases.items()
                                 if k in self._entries}

    def _capture_ivm(self, plan) -> Optional[_IvmState]:
        """Execute the partial aggregate (host backend: IVM partials are
        order-safe exact dtypes, so host == device bit-for-bit) and
        resolve each base table's probe-side scan."""
        path, agg = streaming._path_to_aggregate(plan)
        if agg is None:
            return None
        if any(isinstance(n, P.MaterializedNode)
               for n in P.iter_plan_nodes(agg.child)):
            return None
        partial_specs, recipes, p_names, p_dtypes = streaming._decompose(agg)
        partial_plan = P.AggregateNode(
            child=agg.child, group_exprs=list(agg.group_exprs),
            aggs=list(partial_specs), out_names=list(p_names),
            out_dtypes=list(p_dtypes))
        partial = _execute_plan(self.session, partial_plan,
                                use_jax=False)
        scan_by_table = {}
        for t in {n.table for n in P.iter_plan_nodes(agg.child)
                  if isinstance(n, P.ScanNode)}:
            sn = _probe_scan(agg.child, t)
            if sn is not None:
                scan_by_table[t] = sn
        return _IvmState(agg, path, partial_specs, recipes, p_names,
                         p_dtypes, partial, partial_plan, scan_by_table)

    # -- incremental view maintenance ----------------------------------------
    def apply_delta(self, table: str, inserts=None, deletes=None) -> None:
        """Absorb one maintenance statement's row delta (Session
        ``_publish_table_delta``): entries over `table` either UPDATE in
        place (mergeable partials + probe-side scan) or invalidate.
        Called after the warehouse commit re-registered the table, so the
        expected generation pattern is `table` at current-1 and every
        other base table unmoved."""
        session = self.session
        with self._lock:
            items = [(k, e) for k, e in self._entries.items()
                     if table in e.tables]
        for key, entry in items:
            new_entry = None
            try:
                new_entry = self._updated_entry(entry, table, inserts,
                                                deletes)
            except Exception as e:   # degradation: invalidate, observable
                FLIGHT.record("cache_ivm", status="failed", table=table,
                              error=type(e).__name__)
                new_entry = None
            with self._lock:
                if self._entries.get(key) is not entry:
                    continue          # replaced/evicted mid-flight
                if new_entry is None:
                    self._drop_locked(key, f"delta:{table}")
                else:
                    self._entries[key] = new_entry
            if new_entry is not None:
                _metrics.RESULT_CACHE_IVM_UPDATES.inc()
                FLIGHT.record("cache_ivm", status="updated", table=table,
                              template=str(entry.template_key)[:12])

    def _updated_entry(self, entry: _Entry, table: str, inserts,
                       deletes) -> Optional[_Entry]:
        session = self.session
        if not (self.config.ivm and entry.ivm is not None):
            return None
        gen = session.table_generation
        # exactly one statement behind on the delta table, current on the
        # rest — anything else means a delta was missed: invalidate
        for t, g in entry.gens.items():
            want = gen(t) - 1 if t == table else gen(t)
            if g != want:
                return None
        st = entry.ivm
        partial = st.partial
        if deletes is not None and deletes.num_rows:
            partial = self._ivm_delete(st, partial, table, deletes)
            if partial is None:
                return None
        if inserts is not None and inserts.num_rows:
            partial = self._ivm_insert(st, partial, table, inserts)
            if partial is None:
                return None
        use_jax = entry.backend == "jax"
        mat = P.MaterializedNode(table=partial, label="ivm-partials",
                                 out_names=list(st.p_names),
                                 out_dtypes=list(st.p_dtypes))
        final_b = streaming._final_builder(st.agg, st.recipes, st.p_names,
                                           st.p_dtypes)
        result = _execute_plan(
            session, streaming.rebuild_above(st.path, final_b(mat)),
            use_jax)
        new_ivm = _IvmState(st.agg, st.path, st.partial_specs, st.recipes,
                            st.p_names, st.p_dtypes, partial,
                            st.partial_plan, st.scan_by_table)
        gens = {t: gen(t) for t in entry.gens}
        snaps = {}
        for t in entry.tables:
            sv = session.table_snapshot_version(t)
            if sv is not None:
                snaps[t] = sv
        return _Entry(entry.key, entry.template_key, entry.family,
                      entry.pvalues, entry.backend, result,
                      entry.out_names, entry.out_dtypes, entry.tables,
                      gens, snaps, time.time(), entry.plan, new_ivm)

    def _delta_table(self, scan, arrow_rows):
        """Arrow delta rows -> engine Table in the scan's projection; the
        engine dtypes must match the scan's declared dtypes exactly (a
        drifted staging schema invalidates instead of merging garbage)."""
        from . import arrow_bridge

        t = arrow_bridge.from_arrow(arrow_rows.select(list(scan.columns)),
                                    self.session._dec_as_int())
        got = [c.dtype for c in t.columns]
        if got != list(scan.out_dtypes):
            raise ValueError(f"delta dtypes {got} != scan "
                             f"{list(scan.out_dtypes)}")
        return t

    def _ivm_insert(self, st: _IvmState, partial, table, inserts):
        """Merge inserted-row partials: the aggregate distributes over a
        probe-side row union, so partial(old ∪ delta) = combine(
        partial(old) ∪ partial(delta)) — and every merged column is an
        order-insensitive dtype, so the combine is bit-stable."""
        scan = st.scan_by_table.get(table)
        if scan is None:
            return None
        mat = P.MaterializedNode(table=self._delta_table(scan, inserts),
                                 label="ivm-delta",
                                 out_names=list(scan.columns),
                                 out_dtypes=list(scan.out_dtypes))
        dplan = streaming.substitute_nodes(st.partial_plan,
                                           {id(scan): mat})
        delta_partial = _execute_plan(self.session, dplan,
                                      use_jax=False)
        if delta_partial.num_rows == 0:
            return partial
        merged = self._concat_partials(st, [partial, delta_partial])
        combine = streaming._combine_builder(
            st.agg, st.recipes, st.p_names, st.p_dtypes)
        mat2 = P.MaterializedNode(table=merged, label="ivm-merge",
                                  out_names=list(st.p_names),
                                  out_dtypes=list(st.p_dtypes))
        return Executor(_no_load).execute(combine(mat2))

    def _ivm_delete(self, st: _IvmState, partial, table, deletes):
        """Recompute only delta-touched groups: the deleted rows' group
        keys name the groups whose partials are stale; every other
        group's rows are untouched, so its partial row is kept verbatim."""
        scan = st.scan_by_table.get(table)
        if scan is None:
            return None
        mat = P.MaterializedNode(table=self._delta_table(scan, deletes),
                                 label="ivm-delta",
                                 out_names=list(scan.columns),
                                 out_dtypes=list(scan.out_dtypes))
        dplan = streaming.substitute_nodes(st.partial_plan,
                                           {id(scan): mat})
        touched = _execute_plan(self.session, dplan, use_jax=False)
        if touched.num_rows == 0:
            return partial       # deletes never reached the aggregate
        ngroups = len(st.agg.group_exprs)
        child = st.agg.child

        def key_pred(exprs):
            """Membership predicate over the touched group-key value
            sets (per-column: a cartesian superset — over-inclusive
            recomputation is correct, just wider)."""
            pred = None
            for i in range(ngroups):
                vals, has_null = _col_values(touched.columns[i])
                e = exprs[i]
                c = None
                if vals:
                    c = P.BCall("bool", "in_list", [e], extra=vals)
                if has_null:
                    isn = P.BCall("bool", "isnull", [e])
                    c = isn if c is None else P.BCall("bool", "or",
                                                      [c, isn])
                if c is None:
                    continue
                pred = c if pred is None else P.BCall("bool", "and",
                                                      [pred, c])
            return pred

        child_pred = key_pred(st.agg.group_exprs)
        if child_pred is None:
            return None
        recompute = P.AggregateNode(
            child=P.FilterNode(child, child_pred,
                               out_names=list(child.out_names),
                               out_dtypes=list(child.out_dtypes)),
            group_exprs=list(st.agg.group_exprs),
            aggs=list(st.partial_specs),
            out_names=list(st.p_names), out_dtypes=list(st.p_dtypes))
        recomputed = _execute_plan(self.session, recompute,
                                   use_jax=False)
        # keep every partial row whose group the delta did NOT touch.
        # Three-valued logic: an untouched NULL-keyed group evaluates
        # `key IN (...)` to NULL, and NOT(NULL) would silently drop
        # it — coalesce the membership to FALSE first so "not
        # touched" keeps NULL verdicts
        part_pred = key_pred([P.BCol(st.p_dtypes[i], i, st.p_names[i])
                              for i in range(ngroups)])
        keep = P.FilterNode(
            P.MaterializedNode(table=partial, label="ivm-partials",
                               out_names=list(st.p_names),
                               out_dtypes=list(st.p_dtypes)),
            P.BCall("bool", "not",
                    [P.BCall("bool", "coalesce",
                             [part_pred, P.BLit("bool", False)])]),
            out_names=list(st.p_names), out_dtypes=list(st.p_dtypes))
        kept = Executor(_no_load).execute(keep)
        return self._concat_partials(st, [kept, recomputed])

    def _concat_partials(self, st: _IvmState, parts: list):
        from . import arrow_bridge

        arrow = pa.concat_tables(
            [arrow_bridge.to_arrow(p) for p in parts if p.num_rows]
            or [arrow_bridge.to_arrow(parts[0])],
            promote_options="permissive")
        return arrow_bridge.from_arrow(arrow, self.session._dec_as_int())

    # -- maintenance / introspection -----------------------------------------
    def invalidate_table(self, table: str) -> int:
        """Drop every entry over `table` (manual escape hatch)."""
        with self._lock:
            keys = [k for k, e in self._entries.items()
                    if table in e.tables]
            for k in keys:
                self._drop_locked(k, "manual")
        return len(keys)

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- convenience ---------------------------------------------------------
    def run(self, sql: str, label: Optional[str] = None,
            backend: Optional[str] = None):
        """Lookup-or-execute one text through this cache (the service
        wires the same three steps across its stages; direct engine
        callers and tests use this)."""
        hit = self.lookup_text(sql)
        if hit is not None:
            return hit.table
        plan, fp, pvalues, use_jax = plan_for_cache(self.session, sql,
                                                    backend)
        hit = self.lookup_plan(sql, plan, fp, pvalues, use_jax)
        if hit is not None:
            return hit.table
        gens = self.snapshot_gens(plan)
        table, _stats = self.session.service_run(sql, backend=backend,
                                                 label=label, plan=plan)
        self.store(sql, plan, fp, pvalues, table, use_jax=use_jax,
                   gens=gens)
        return table
