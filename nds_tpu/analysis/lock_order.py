"""ENG003 — whole-program lock-order deadlock detection.

The engine holds 25+ locks across session/service/frontdoor/cache/
metrics; a deadlock needs only two threads acquiring two of them in
opposite orders. This pass makes the acquisition ORDER a static,
CI-gated property:

1. every ``with <lock>:`` site is canonicalized to the lock OBJECT it
   names (``self._lock`` inside ``Session`` and ``session._lock`` from a
   service thread are the same node; ``Counter._lock`` aliases the
   metrics registry's shared value lock it was constructed with);
2. nested acquisitions add edges held-lock -> acquired-lock, and calls
   made while holding a lock add edges to every lock the callee may
   (transitively) acquire — resolved through the per-module summary
   pass's program-wide function index;
3. the resulting graph must be acyclic AND respect the declared
   hierarchy table below (an edge from an inner lock back out to an
   outer one is flagged even before a second thread closes the cycle).

``# lint: lock-order-exempt (<reason>)`` on the acquisition (or call)
line drops that edge — the audited exceptions.

The declared hierarchy (outer acquired first, LOWER level number):

====  ======================================================================
  10  ``QueryService._cv`` — service scheduler state (admission, queues)
  15  ``Ticket._mat_lock`` — per-ticket deferred materialization cell
  20  ``Session._sql_lock`` — whole-statement serialization (device lane)
  30  ``Session._lock`` — session shared caches (stats/loaders/streams)
  40  ``executor._SHARED_LOCK`` — cross-stream shared-program registry
  42  ``CompiledQuery._lock`` / ``BatchedQuery._lock`` — per-program state
  44  ``ShardedMorselQuery._lock`` — sharded stream bookkeeping
  50  leaf stores: ``ResultCache._lock``, ``QueryLog._lock``,
      ``FaultRegistry._lock``, ``CircuitBreaker._lock``,
      ``DeviceMemTracker._lock``, ``resilience._ABANDONED_LOCK``
  55  observability sinks callable from under any leaf store:
      ``FlightRecorder._lock``, ``Tracer._lock``
  60  ``MetricsRegistry._lock`` — metric registration
  70  ``MetricsRegistry._values`` — the shared value lock (innermost:
      every counter inc lands here, so everything may hold-and-enter)
====  ======================================================================
"""
from __future__ import annotations

import os
from dataclasses import dataclass

from .base import Finding, suggestion_for
from .summary import CallSite, FunctionSummary, ProgramSummary

#: lock attribute names unique enough to identify the object program-wide
UNIQUE_LOCK_ATTRS = {
    "_sql_lock": "Session._sql_lock",
    "_values": "MetricsRegistry._values",
    "locked": "MetricsRegistry._values",       # METRICS.locked() accessor
    "_SHARED_LOCK": "executor._SHARED_LOCK",
    "_ABANDONED_LOCK": "resilience._ABANDONED_LOCK",
    "_mat_lock": "Ticket._mat_lock",
    "_cv": "QueryService._cv",
}

#: receiver-variable spellings that identify the owning class of a
#: generic ``_lock`` attribute when the write is not through ``self``
VAR_CLASS_HINTS = {
    "session": "Session",
    "registry": "MetricsRegistry",
    "cache": "ResultCache",
    "ticket": "Ticket",
}

#: module-level singletons: an ALL_CAPS receiver pins the callee class
#: exactly, so ``FLIGHT.record(...)`` resolves to FlightRecorder.record
#: instead of every ``record`` method in the program
CONST_CLASS_HINTS = {
    "FLIGHT": "FlightRecorder",
    "TRACER": "Tracer",
    "METRICS": "MetricsRegistry",
    "QUERY_LOG": "QueryLog",
    "DEVICE_MEM": "DeviceMemTracker",
}

#: classes whose ``self._lock`` IS another class's canonical lock (the
#: metrics registry hands every Counter/Gauge/Histogram its shared value
#: lock, so their method bodies acquire MetricsRegistry._values)
LOCK_CLASS_ALIASES = {
    "Counter": "MetricsRegistry._values",
    "Gauge": "MetricsRegistry._values",
    "Histogram": "MetricsRegistry._values",
}

#: declared hierarchy: canonical lock -> level (outer = lower). Every
#: observed edge must go strictly downward (outer -> inner). Locks absent
#: from this table participate in cycle detection only.
LOCK_LEVELS = {
    "QueryService._cv": 10,
    "Ticket._mat_lock": 15,
    "Session._sql_lock": 20,
    "Session._lock": 30,
    "executor._SHARED_LOCK": 40,
    "CompiledQuery._lock": 42,
    "BatchedQuery._lock": 42,
    "ShardedMorselQuery._lock": 44,
    "ResultCache._lock": 50,
    "QueryLog._lock": 50,
    "FaultRegistry._lock": 50,
    "CircuitBreaker._lock": 50,
    "DeviceMemTracker._lock": 50,
    "resilience._ABANDONED_LOCK": 50,
    "FlightRecorder._lock": 55,
    "Tracer._lock": 55,
    "MetricsRegistry._lock": 60,
    "MetricsRegistry._values": 70,
}

#: method names too generic to resolve by name across the program —
#: calls through them are not followed (a dict ``.get`` must not alias
#: ``ResultCache.get``). Distinctive engine entry points stay followable.
GENERIC_METHOD_NAMES = frozenset({
    "get", "put", "set", "add", "pop", "popleft", "append", "appendleft",
    "extend", "update", "insert", "remove", "discard", "clear", "copy",
    "items", "keys", "values", "sort", "split", "join", "strip", "read",
    "write", "flush", "close", "open", "send", "recv", "encode", "decode",
    "wait", "notify", "notify_all", "acquire", "release", "start", "run",
    "result", "done", "next", "submit", "map", "format", "count", "index",
    "setdefault", "sum", "min", "max", "mean", "render", "name", "group",
})


def canonical_lock(raw: str, cls: str, module: str) -> str:
    """Canonical node name for one lock spelling at one site."""
    attr = raw.rsplit(".", 1)[-1]
    root = raw.split(".", 1)[0]
    if attr in UNIQUE_LOCK_ATTRS:
        return UNIQUE_LOCK_ATTRS[attr]
    owner = None
    if root == "self" and cls:
        owner = cls
    elif root in VAR_CLASS_HINTS:
        owner = VAR_CLASS_HINTS[root]
    if owner is not None:
        alias = LOCK_CLASS_ALIASES.get(owner)
        if alias:
            return alias
        return f"{owner}.{attr}"
    # unresolved receiver: a per-module node that cannot alias another
    # class's lock (sound for cycle detection, invisible to levels)
    base = os.path.basename(module)
    return f"?{base}:{raw}"


@dataclass
class _Edge:
    src: str
    dst: str
    path: str
    line: int
    via: str          # '' for a lexical nesting, else the callee chain
    exempt: bool


def _resolve_call(cs: CallSite, fn: FunctionSummary,
                  prog: ProgramSummary) -> list[FunctionSummary]:
    """Best-effort static callee resolution (union semantics — the
    over-approximation is what makes the edge set a superset of the real
    acquisition graph)."""
    if cs.is_self and fn.cls:
        found = prog.methods_of(fn.cls, cs.name)
        if found:
            return found
        return []
    if cs.is_bare:
        same_mod = [f for f in prog.by_name.get(cs.name, ())
                    if f.module == fn.module and not f.cls]
        if same_mod:
            return same_mod
        glob = [f for f in prog.by_name.get(cs.name, ()) if not f.cls]
        return glob if len(glob) == 1 else []
    # x.m(...): a known receiver pins the class exactly (and overrides
    # the generic-name stoplist — the receiver disambiguates)
    if cs.recv_root in CONST_CLASS_HINTS:
        return prog.methods_of(CONST_CLASS_HINTS[cs.recv_root], cs.name)
    if cs.recv_root in VAR_CLASS_HINTS:
        found = prog.methods_of(VAR_CLASS_HINTS[cs.recv_root], cs.name)
        if found:
            return found
    # otherwise follow only distinctive method names
    if cs.name in GENERIC_METHOD_NAMES:
        return []
    return [f for f in prog.by_name.get(cs.name, ()) if f.cls]


def _transitive_acquires(prog: ProgramSummary) -> dict[int, set[str]]:
    """id(fn) -> canonical locks the function may acquire, directly or
    through resolved callees (fixpoint union)."""
    direct: dict[int, set[str]] = {}
    callees: dict[int, list[int]] = {}
    for fn in prog.functions:
        direct[id(fn)] = {canonical_lock(la.raw, la.cls, fn.module)
                          for la in fn.locks}
        callees[id(fn)] = [id(g) for cs in fn.calls
                           for g in _resolve_call(cs, fn, prog)]
    acq = {k: set(v) for k, v in direct.items()}
    changed = True
    while changed:
        changed = False
        for k, cs in callees.items():
            merged = acq[k]
            before = len(merged)
            for c in cs:
                merged |= acq.get(c, set())
            if len(merged) != before:
                changed = True
    return acq


def _build_edges(prog: ProgramSummary) -> list[_Edge]:
    acq = _transitive_acquires(prog)
    edges: list[_Edge] = []
    for fn in prog.functions:
        for la in fn.locks:
            dst = canonical_lock(la.raw, la.cls, fn.module)
            for h in la.held:
                src = canonical_lock(h, fn.cls, fn.module)
                if src != dst:
                    edges.append(_Edge(src, dst, fn.module, la.line, "",
                                       la.exempt))
        for cs in fn.calls:
            if not cs.held:
                continue
            targets = _resolve_call(cs, fn, prog)
            if not targets:
                continue
            dsts: set[str] = set()
            for g in targets:
                dsts |= acq.get(id(g), set())
            for h in cs.held:
                src = canonical_lock(h, fn.cls, fn.module)
                for dst in dsts:
                    if src != dst:
                        edges.append(_Edge(src, dst, fn.module, cs.line,
                                           cs.dot or cs.name,
                                           cs.lock_exempt))
    return edges


def _find_cycles(edges: list[_Edge]) -> list[list[_Edge]]:
    """Edges participating in cycles, grouped per strongly-connected
    component with >1 node (or a self-loop)."""
    graph: dict[str, set[str]] = {}
    for e in edges:
        graph.setdefault(e.src, set()).add(e.dst)
        graph.setdefault(e.dst, set())
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    sccs: list[set[str]] = []
    counter = [0]

    def strongconnect(v: str) -> None:  # iterative Tarjan
        work = [(v, iter(sorted(graph[v])))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(graph[w]))))
                    advanced = True
                    break
                if w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc = set()
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.add(w)
                    if w == node:
                        break
                sccs.append(scc)

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    out = []
    for scc in sccs:
        if len(scc) > 1:
            out.append([e for e in edges
                        if e.src in scc and e.dst in scc])
    return out


def check_lock_order(prog: ProgramSummary) -> list[Finding]:
    edges = _build_edges(prog)
    findings: list[Finding] = []
    sug = suggestion_for("ENG003")

    # 1. hierarchy: every live edge between DECLARED locks goes outer ->
    #    inner (strictly downward in level)
    seen: set[tuple] = set()
    for e in edges:
        la, lb = LOCK_LEVELS.get(e.src), LOCK_LEVELS.get(e.dst)
        if la is None or lb is None or la < lb:
            continue
        key = (e.src, e.dst, e.path, e.line)
        if key in seen:
            continue
        seen.add(key)
        via = f" (via {e.via})" if e.via else ""
        rel = "same-level" if la == lb else "inverted"
        findings.append(Finding(
            e.path, e.line, 0, "ENG003",
            f"lock-order violation: acquiring '{e.dst}' (level {lb}) "
            f"while holding '{e.src}' (level {la}){via} — the declared "
            f"hierarchy (analysis/lock_order.py) is {rel} here; reorder "
            "the acquisitions or exempt the audited site",
            suggestion=sug, suppressed=e.exempt))

    # 2. cycles over the live (non-exempt) edge set — a cycle among
    #    undeclared locks deadlocks just as hard
    live = [e for e in edges if not e.exempt]
    for cyc in _find_cycles(live):
        nodes = " -> ".join(sorted({e.src for e in cyc}))
        reported: set[tuple] = set()
        for e in cyc:
            key = (e.src, e.dst, e.path, e.line)
            if key in reported:
                continue
            reported.add(key)
            via = f" (via {e.via})" if e.via else ""
            findings.append(Finding(
                e.path, e.line, 0, "ENG003",
                f"lock-acquisition cycle [{nodes}]: this edge "
                f"'{e.src}' -> '{e.dst}'{via} closes an order two "
                "threads can interleave into a deadlock",
                suggestion=sug))
    return findings
