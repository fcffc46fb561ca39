"""EXPLAIN ANALYZE: per-plan-node runtime profiles + device-memory watermarks.

PR 6 attributes device time to whole compiled programs and the service
layer keeps per-tenant latency histograms — but when a template regresses
nothing could say *which plan operator* is responsible, whether the
planner's static size assumptions matched reality, or how close a query
came to the device-memory ceiling. This module is that missing layer
(the per-operator profiling discipline "Accelerating Presto with GPUs"
and Flare treat as table stakes, PAPERS.md):

- :func:`plan_tree` — stable per-plan-node identities: the SAME
  ``TypeName#k`` preorder labels ``engine/verify.py`` anchors findings to
  (``node_labels``), so profiles, verifier findings, and
  ``ExecStats.node_stats`` all name the same node;
- :class:`PlanProfile` / :class:`NodeStat` — the profile artifact one
  profiled execution produces (``Session.explain_analyze`` /
  ``EngineConfig.profile_plans``): per node wall/rows/bytes, estimate
  beside actual, serializable (``to_dict``/``from_dict``) so runners can
  embed it in JSON summaries and ``scripts/explain_report.py`` can render
  it offline;
- :func:`estimate_rows` — the planner's STATIC size assumptions re-derived
  per node (scan = catalog est_rows, join = probe-side bound, capacity =
  the ladder bucket of the estimate), the "expected" side of the audit;
- :func:`cardinality_audit` — estimate-vs-actual diff flagging
  misestimates above a ratio threshold as structured findings (with the
  capacity-ladder bucket drift that actually costs recompiles/memory);
- :func:`render_profile` — the annotated plan tree (time %, rows
  est->act, bytes, memory peak) ``power --explain`` prints;
- :data:`DEVICE_MEM` — device-memory watermark accountant threaded
  through ``device.to_device``/``pack_table``/``stage_sharded`` and the
  codebook cache: live set, process peak, and per-query window peaks
  surfaced as gauges (``device_live_bytes``/``device_peak_bytes``), in
  ``ExecStats.mem_*``, and as the ``memory`` block in bench JSON.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Optional


# --------------------------------------------------------------------------
# device-memory watermark accounting
# --------------------------------------------------------------------------

class DeviceMemTracker:
    """Accounting of TRACKED device allocations, not a full HBM profiler.

    Tracked: every upload through ``device.to_device`` / ``pack_table`` /
    ``shard_exec.stage_sharded`` and the device codebook cache; frees
    through ``device.free_dtable`` (and codebook-cache resets) subtract.
    NOT tracked: compiled-program intermediates and outputs — XLA owns
    those, and the engine's memory lever is the upload/scan live set this
    tracker watches (the scan-budget eviction operates on exactly it).

    Buffers are tracked by leaf-array identity, so a double add or a free
    of an untracked tree (segment outputs, device-computed tables) never
    corrupts the balance; buffers dropped to the GC without an explicit
    ``free_dtable`` stay counted until process end (documented drift —
    the engine frees every hot-loop buffer explicitly).

    ``mark_window()``/``window_peak()`` give per-query peaks: the session
    marks at statement start (under its statement lock, so windows never
    interleave) and reads the window's high-water mark into
    ``ExecStats.mem_peak_bytes`` at finish.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._leaves: dict[int, int] = {}   # id(device array) -> bytes
        self.live = 0
        self.peak = 0
        self._win_peak = 0

    def _gauges(self, live: int, peak: int) -> None:
        from . import metrics as _m
        _m.DEVICE_LIVE_BYTES.set(live)
        _m.DEVICE_PEAK_BYTES.set(peak)

    def add(self, leaves) -> None:
        """Track [(id, nbytes)] device-array leaves (untracked ids only)."""
        with self._lock:
            for i, b in leaves:
                if i not in self._leaves:
                    self._leaves[i] = b
                    self.live += b
            if self.live > self.peak:
                self.peak = self.live
            if self.live > self._win_peak:
                self._win_peak = self.live
            live, peak = self.live, self.peak
        self._gauges(live, peak)

    def free(self, leaves) -> None:
        """Untrack [(id, nbytes)] leaves; ids never tracked are ignored."""
        with self._lock:
            for i, _b in leaves:
                b = self._leaves.pop(i, None)
                if b is not None:
                    self.live -= b
            live, peak = self.live, self.peak
        self._gauges(live, peak)

    def mark_window(self) -> None:
        """Open a per-query peak window (statement start)."""
        with self._lock:
            self._win_peak = self.live

    def window_peak(self) -> int:
        """High-water mark of the live set since ``mark_window``."""
        with self._lock:
            return self._win_peak

    def restore_window(self, saved_peak: int) -> None:
        """Re-open a suspended statement's peak window (the service's
        morsel-boundary preemption nests a statement inside another):
        the resumed window's peak is the max of what the outer statement
        had already seen and everything since — the outer statement's
        mem_peak_bytes keeps covering its whole wall."""
        with self._lock:
            self._win_peak = max(saved_peak, self._win_peak)

    def reset(self) -> None:
        """Zero all accounting (tests only)."""
        with self._lock:
            self._leaves.clear()
            self.live = 0
            self.peak = 0
            self._win_peak = 0
        self._gauges(0, 0)


#: the process-global device-memory accountant (device.py writes through)
DEVICE_MEM = DeviceMemTracker()


def memory_block(budget_bytes: Optional[int] = None) -> dict:
    """The ``memory`` block runners embed in their JSON output: live set,
    process peak, and (when the HBM budget is known) headroom between the
    peak and the budget."""
    out = {"device_live_bytes": DEVICE_MEM.live,
           "device_peak_bytes": DEVICE_MEM.peak}
    if budget_bytes:
        out["budget_bytes"] = int(budget_bytes)
        out["headroom_bytes"] = int(budget_bytes) - DEVICE_MEM.peak
    return out


# --------------------------------------------------------------------------
# plan-node identities + tree structure
# --------------------------------------------------------------------------

def _subquery_plans(node) -> list:
    """Plans DIRECTLY embedded in this node's expressions
    (BScalarSubquery roots reachable without crossing another PlanNode),
    in deterministic field order — they render as extra children of the
    node whose expression consumes them."""
    import dataclasses as _dc

    from ..engine import plan as P

    out: list = []

    def rec(x):
        if isinstance(x, P.BScalarSubquery):
            out.append(x.plan)
            return
        if isinstance(x, P.PlanNode) or isinstance(x, (str, int, float,
                                                       bool)) or x is None:
            return
        if _dc.is_dataclass(x) and not isinstance(x, type):
            for f in P.type_fields(x):
                rec(getattr(x, f))
        elif isinstance(x, (list, tuple)):
            for v in x:
                rec(v)

    for f in ("predicate", "exprs", "left_keys", "right_keys", "residual",
              "group_exprs", "aggs", "funcs", "keys"):
        if hasattr(node, f):
            rec(getattr(node, f))
    return out


def plan_tree(root):
    """(labels, children, order) for a plan DAG.

    - ``labels``: ``{id(node): "TypeName#k"}`` — verify.node_labels, the
      SAME stable preorder identity verifier findings use, preserved for
      free through rewrite passes because it is a pure function of the
      final plan's structure (two structurally identical plans label
      identically, parameterization does not change node order);
    - ``children``: ``{label: [child label, ...]}`` — plan fields
      (child/left/right) first, then expression-embedded subquery roots;
    - ``order``: distinct nodes children-first (post-order) — the safe
      execution order for a node-by-node profiled walk (every child is
      memoized before its parent runs).
    """
    from ..engine import plan as P
    from ..engine.verify import node_labels

    labels = node_labels(root)
    children: dict[str, list[str]] = {}
    order: list = []
    seen: set[int] = set()

    def kids(n) -> list:
        out = []
        for f in ("child", "left", "right"):
            sub = getattr(n, f, None)
            if isinstance(sub, P.PlanNode):
                out.append(sub)
        out.extend(_subquery_plans(n))
        return out

    def visit(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        ks = kids(n)
        children[labels[id(n)]] = [labels[id(k)] for k in ks]
        for k in ks:
            visit(k)
        order.append(n)

    visit(root)
    return labels, children, order


def node_detail(node) -> str:
    """Short human detail for one node: scan table, join kind, agg arity."""
    t = type(node).__name__
    if t == "ScanNode":
        return node.table
    if t == "JoinNode":
        return node.kind + "".join(
            mark for mark, flag in (("+late_mat", "late_mat"),
                                    ("+star", "star_build"))
            if getattr(node, flag, False))
    if t == "AggregateNode":
        return f"{len(node.group_exprs)}g/{len(node.aggs)}a" + \
            ("+rollup" if node.rollup else "")
    if t == "LimitNode":
        return str(node.n)
    if t == "SetOpNode":
        return node.op + (" all" if node.all else "")
    if t in ("MaterializedNode", "VirtualScanNode"):
        return getattr(node, "label", "") or getattr(node, "key", "")
    return ""


# --------------------------------------------------------------------------
# static row estimates (the planner's size assumptions)
# --------------------------------------------------------------------------

def estimate_rows(root, est_rows_fn: Callable[[str], Optional[int]]
                  ) -> dict[int, Optional[int]]:
    """{id(node): estimated output rows} from the planner's STATIC stats —
    the same inputs streaming thresholds, the capacity ladder, and the
    late-mat size gate consult (catalog est_rows per scan; no per-node
    selectivity model exists, so non-scan estimates are the structural
    upper bounds capacity planning actually assumes). None = unknown
    (virtual scans whose source is another compile unit)."""
    from ..engine import plan as P

    memo: dict[int, Optional[int]] = {}

    def est(n) -> Optional[int]:
        if id(n) in memo:
            return memo[id(n)]
        memo[id(n)] = None          # cycle guard (plans are DAGs, not cyclic)
        t = type(n).__name__
        out: Optional[int]
        if isinstance(n, P.ScanNode):
            out = est_rows_fn(n.table)
        elif isinstance(n, P.MaterializedNode):
            out = n.table.num_rows          # already computed: exact
        elif t == "VirtualScanNode":
            out = None
        elif isinstance(n, P.JoinNode):
            le, ri = est(n.left), est(n.right)
            if le is None or ri is None:
                out = None
            elif n.kind == "cross":
                out = le * ri
            elif n.kind in ("semi", "anti"):
                out = le
            elif n.kind == "full":
                out = le + ri
            else:       # inner/left/right: the probe-side (fact) bound
                out = max(le, ri)
        elif isinstance(n, P.SetOpNode):
            le, ri = est(n.left), est(n.right)
            if le is None or ri is None:
                out = None
            else:
                out = le + ri if n.op == "union" else le
        elif isinstance(n, P.LimitNode):
            c = est(n.child)
            out = n.n if c is None else min(n.n, c)
        else:
            c = getattr(n, "child", None)
            out = est(c) if c is not None else None
        memo[id(n)] = out
        return out

    for n in P.iter_plan_nodes(root):
        est(n)
    return memo


# --------------------------------------------------------------------------
# the profile artifact
# --------------------------------------------------------------------------

@dataclass
class NodeStat:
    """One plan node's profiled execution record."""
    label: str                      # stable TypeName#k identity
    op: str                         # node type name
    detail: str = ""                # table / join kind / agg arity
    est_rows: Optional[int] = None  # planner static estimate
    rows: Optional[int] = None      # exact actual output rows
    wall_ms: Optional[float] = None  # this node's own wall (children memoized)
    bytes: Optional[int] = None     # device bytes of the node's output
    children: list = field(default_factory=list)   # child labels

    def to_dict(self) -> dict:
        out = {"label": self.label, "op": self.op}
        for k in ("detail", "est_rows", "rows", "wall_ms", "bytes"):
            v = getattr(self, k)
            if v not in (None, ""):
                out[k] = v
        if self.children:
            out["children"] = list(self.children)
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "NodeStat":
        return cls(label=d["label"], op=d.get("op", "?"),
                   detail=d.get("detail", ""), est_rows=d.get("est_rows"),
                   rows=d.get("rows"), wall_ms=d.get("wall_ms"),
                   bytes=d.get("bytes"),
                   children=list(d.get("children", ())))


@dataclass
class PlanProfile:
    """One profiled execution: the annotated plan tree + audit + memory.

    ``nodes`` keys are the stable TypeName#k labels; ``root`` names the
    plan root. ``table`` (not serialized) holds the result Table of the
    profiled run — bit-identical to unprofiled execution by construction
    (the profiled walk runs the SAME executor eagerly; the streamed path
    runs completely unchanged and only reads counters)."""
    query: str = ""                 # label (query9, ...)
    backend: str = "jax"
    mode: str = "in-core"           # in-core | streaming | numpy
    total_ms: float = 0.0           # profiled execution wall
    root: str = ""
    nodes: dict = field(default_factory=dict)     # label -> NodeStat
    findings: list = field(default_factory=list)  # cardinality audit
    memory: dict = field(default_factory=dict)    # watermark block
    table: object = None            # result Table (not serialized)

    def profiled_ms(self) -> float:
        """Sum of per-node walls (acceptance: >= 90% of total_ms for the
        eager in-core walk — everything outside is plan/merge glue)."""
        return sum(ns.wall_ms or 0.0 for ns in self.nodes.values())

    def to_dict(self) -> dict:
        return {"profile_version": 1, "query": self.query,
                "backend": self.backend, "mode": self.mode,
                "total_ms": round(self.total_ms, 3), "root": self.root,
                "nodes": {k: v.to_dict() for k, v in self.nodes.items()},
                "findings": list(self.findings),
                "memory": dict(self.memory)}

    @classmethod
    def from_dict(cls, d: dict) -> "PlanProfile":
        return cls(query=d.get("query", ""), backend=d.get("backend", ""),
                   mode=d.get("mode", ""), total_ms=d.get("total_ms", 0.0),
                   root=d.get("root", ""),
                   nodes={k: NodeStat.from_dict(v)
                          for k, v in d.get("nodes", {}).items()},
                   findings=list(d.get("findings", ())),
                   memory=dict(d.get("memory", {})))

    def render(self, top_findings: int = 8) -> str:
        return render_profile(self, top_findings=top_findings)


# --------------------------------------------------------------------------
# the estimate-vs-actual cardinality audit
# --------------------------------------------------------------------------

def cardinality_audit(profile: PlanProfile, ratio: float = 4.0) -> list:
    """Structured misestimate findings: nodes whose actual row count
    diverges from the planner's static estimate by at least ``ratio``
    (either direction, +1-smoothed so empty outputs compare sanely).
    Each finding records whether the CAPACITY LADDER bucket drifted too —
    a misestimate inside one bucket costs nothing (same compiled shape,
    same device buffer); across buckets it is the class that recompiles
    programs and over/under-sizes device memory."""
    try:
        from ..engine.jax_backend.device import bucket as _bucket
    except Exception:               # renderer-only environments
        def _bucket(n, minimum=8):
            return n
    findings = []
    for label, ns in profile.nodes.items():
        if ns.est_rows is None or ns.rows is None:
            continue
        est, act = int(ns.est_rows), int(ns.rows)
        r = (est + 1) / (act + 1)
        if r < 1.0:
            r = 1.0 / r
        if r < ratio:
            continue
        b_est = _bucket(max(est, 1))
        b_act = _bucket(max(act, 1))
        findings.append({
            "kind": "misestimate",
            "label": label, "op": ns.op, "detail": ns.detail,
            "est_rows": est, "rows": act, "ratio": round(r, 1),
            "direction": "over" if est > act else "under",
            "bucket_est": b_est, "bucket_act": b_act,
            "bucket_drift": b_est != b_act,
        })
    findings.sort(key=lambda f: (-f["bucket_drift"], -f["ratio"]))
    return findings


# --------------------------------------------------------------------------
# renderer
# --------------------------------------------------------------------------

def _fmt_rows(n: Optional[int]) -> str:
    if n is None:
        return "-"
    if n >= 10_000_000:
        return f"{n / 1e6:.0f}M"
    if n >= 100_000:
        return f"{n / 1e3:.0f}k"
    return str(n)


def _fmt_bytes(b: Optional[int]) -> str:
    if not b:
        return "-"
    if b >= 1 << 30:
        return f"{b / (1 << 30):.2f}GB"
    if b >= 1 << 20:
        return f"{b / (1 << 20):.1f}MB"
    if b >= 1 << 10:
        return f"{b / (1 << 10):.1f}KB"
    return f"{b}B"


def render_profile(p: PlanProfile, top_findings: int = 8) -> str:
    """The annotated plan tree: one line per node with self wall + time%,
    rows est->act, output bytes; shared (DAG) subtrees print once and
    later references point back. Findings and the memory watermark block
    follow the tree."""
    total = p.total_ms or 1e-9
    flagged = {f["label"] for f in p.findings}
    lines = [f"{p.query or 'query'}  [{p.backend}/{p.mode}]  "
             f"total {p.total_ms:.1f} ms, per-node "
             f"{p.profiled_ms():.1f} ms "
             f"({100.0 * p.profiled_ms() / total:.0f}%)"]
    printed: set[str] = set()

    def line(label: str, prefix: str, tail: str) -> None:
        ns = p.nodes.get(label)
        if ns is None:
            lines.append(f"{prefix}{label} (not executed)")
            return
        name = f"{ns.op.replace('Node', '')}#{label.rsplit('#', 1)[-1]}"
        if ns.detail:
            name += f"[{ns.detail}]"
        if label in printed:
            lines.append(f"{prefix}{name} (shared, profiled above)")
            return
        printed.add(label)
        wall = ns.wall_ms or 0.0
        pct = 100.0 * wall / total
        est = _fmt_rows(ns.est_rows)
        act = _fmt_rows(ns.rows)
        flag = "  <-- misestimate" if label in flagged else ""
        lines.append(f"{prefix}{name:<{max(44 - len(prefix), 8)}} "
                     f"{wall:>9.1f}ms {pct:>5.1f}%  "
                     f"rows {est:>7}->{act:<7} {_fmt_bytes(ns.bytes):>8}"
                     f"{flag}")
        kids = ns.children
        for i, k in enumerate(kids):
            last = i == len(kids) - 1
            branch = "`-- " if last else "|-- "
            cont = "    " if last else "|   "
            line(k, tail + branch, tail + cont)

    line(p.root, "", "")
    if p.findings:
        lines.append(f"cardinality audit: {len(p.findings)} misestimate(s)"
                     " (worst first; bucket drift = recompile/memory risk)")
        for f in p.findings[:top_findings]:
            drift = (f" bucket {_fmt_rows(f['bucket_est'])}->"
                     f"{_fmt_rows(f['bucket_act'])}"
                     if f.get("bucket_drift") else "")
            det = f"[{f['detail']}]" if f.get("detail") else ""
            lines.append(
                f"  {f['label']}{det}: est "
                f"{_fmt_rows(f['est_rows'])} vs actual "
                f"{_fmt_rows(f['rows'])} ({f['ratio']}x "
                f"{f['direction']}){drift}")
    if p.memory:
        m = p.memory
        head = (f"memory: query peak {_fmt_bytes(m.get('query_peak_bytes'))}"
                f", live {_fmt_bytes(m.get('device_live_bytes'))}"
                f", process peak {_fmt_bytes(m.get('device_peak_bytes'))}")
        if m.get("budget_bytes"):
            head += (f", headroom {_fmt_bytes(m.get('headroom_bytes'))} "
                     f"of {_fmt_bytes(m.get('budget_bytes'))} budget")
        lines.append(head)
    return "\n".join(lines)
