"""The ``system`` catalog: engine introspection as ordinary SQL tables.

Every telemetry surface the stack has grown — histograms and traces
(PR 11), flight/chaos evidence (PR 12), result-cache counters (PR 13),
profiles and memory watermarks (PR 14), the durable query log (this PR)
— was reachable only through Python APIs and offline report scripts.
Production engines dogfood instead ("Accelerating Presto with GPUs"
leans on Presto's ``system.runtime`` tables; PyTond's thesis is that
pushing the analysis INTO the engine beats exporting it), so NDS-TPU
introspects itself through its own SQL path:

    SELECT tenant, wall_ms FROM system.query_log
    SELECT name, value FROM system.metrics WHERE name = 'compiles'
    SELECT le_ms, count FROM system.histograms WHERE tenant = 'dash'

Contract (pinned by tests):

- **Frozen schemas** — ``SYSTEM_SCHEMAS`` lists every table's column
  names and engine dtypes; they change only deliberately.
- **Atomic snapshots** — each provider cuts its registry under that
  registry's own lock (``METRICS.rows()``/``histograms()`` are single
  atomic cuts; the query-log ring and flight ring copy under their
  locks), so a reader racing writers never sees a torn row.
- **Host-only execution** — system statements plan against a dedicated
  catalog and run on the HOST executor over in-memory snapshots: an
  operator's ``SELECT p99 ... GROUP BY tenant`` never touches the device
  lane, the planner worker pool, or any compiled-program cache, and so
  never perturbs the workload it is measuring. ``QueryService.submit``
  routes these around admission (observability must work DURING overload
  and open circuits).

The snapshot is taken per statement — polling re-reads live state.
"""
from __future__ import annotations

import json
from typing import Callable, Optional

import pyarrow as pa

from .flight import FLIGHT
from .metrics import METRICS
from .query_log import COLUMNS as _QL_COLUMNS
from .query_log import QUERY_LOG

#: catalog prefix; a statement whose tables ALL carry it is a system
#: statement (mixing system.* with user tables is rejected — the host
#: snapshot executor must never pull warehouse-scale data)
PREFIX = "system."

_ARROW = {"int": pa.int64(), "float": pa.float64(), "str": pa.string(),
          "bool": pa.bool_()}

#: the frozen table schemas: name -> ((columns...), (engine dtypes...)).
SYSTEM_SCHEMAS: dict[str, tuple[tuple, tuple]] = {
    "system.query_log": (
        tuple(c for c, _ in _QL_COLUMNS),
        tuple(t for _, t in _QL_COLUMNS)),
    "system.metrics": (
        ("name", "kind", "value", "help"),
        ("str", "str", "float", "str")),
    "system.histograms": (
        ("name", "series", "tenant", "template", "le_ms", "count",
         "cum_count", "total_count", "sum_ms", "min_ms", "max_ms"),
        ("str", "str", "str", "str", "float", "int",
         "int", "int", "float", "float", "float")),
    "system.programs": (
        ("fingerprint", "hits", "compiles", "strikes", "volatile",
         "nojit", "decisions"),
        ("str", "int", "int", "int", "bool", "bool", "int")),
    "system.result_cache": (
        ("entry", "template", "backend", "rows", "hits", "stored_at",
         "tables", "ivm"),
        ("str", "str", "str", "int", "int", "float", "str", "bool")),
    "system.device_memory": (
        ("metric", "bytes"),
        ("str", "int")),
    "system.flight": (
        ("seq", "t_ms", "event", "label", "tenant", "reason",
         "latency_ms", "detail"),
        ("int", "float", "str", "str", "str", "str", "float", "str")),
    "system.tables": (
        ("name", "generation", "est_rows", "columns", "unique_cols"),
        ("str", "int", "int", "int", "str")),
    "system.snapshots": (
        ("version", "timestamp_ms", "committer", "tables",
         "table_count", "current", "pinned"),
        ("int", "int", "str", "str", "int", "bool", "bool")),
}


def system_table_names() -> tuple:
    return tuple(SYSTEM_SCHEMAS)


def is_system_table(name: str) -> bool:
    return name.startswith(PREFIX)


def catalog_entries() -> dict:
    """{name: (names, dtypes, est_rows)} in the shape the planner's
    Catalog consumes — est_rows is a nominal constant (snapshots are
    bounded rings; no cost model depends on it)."""
    return {name: (list(cols), list(dts), 4096)
            for name, (cols, dts) in SYSTEM_SCHEMAS.items()}


def _arrow(name: str, rows: list[dict]) -> pa.Table:
    cols, dts = SYSTEM_SCHEMAS[name]
    schema = pa.schema([(c, _ARROW[t]) for c, t in zip(cols, dts)])
    return pa.Table.from_pylist(
        [{c: r.get(c) for c in cols} for r in rows], schema=schema)


# -- per-table snapshot providers (each cuts its registry atomically) -------

def _query_log_rows(session) -> list[dict]:
    return QUERY_LOG.rows()


def _metrics_rows(session) -> list[dict]:
    return [{"name": n, "kind": k, "value": float(v), "help": h}
            for n, k, v, h in METRICS.rows()]


def _histogram_rows(session) -> list[dict]:
    """Bucket-level export: one row per nonzero bucket per series (le_ms
    NULL = the +Inf overflow bucket), with the exact count/sum/min/max
    repeated per row so a single SELECT carries everything a quantile
    needs — the same snapshot quantile_from_snapshot consumes."""
    out = []
    for series, snap in METRICS.histograms().items():
        labels = snap.get("labels", {})
        cum = 0
        for le, n in snap.get("buckets", ()):
            cum += n
            out.append({
                "name": snap["name"], "series": series,
                "tenant": labels.get("tenant"),
                "template": labels.get("template"),
                "le_ms": le, "count": n, "cum_count": cum,
                "total_count": snap["count"], "sum_ms": snap["sum"],
                "min_ms": snap["min"], "max_ms": snap["max"]})
    return out


def _program_rows(session) -> list[dict]:
    from ..engine.jax_backend.executor import shared_programs_snapshot
    return shared_programs_snapshot()


def _result_cache_rows(session) -> list[dict]:
    cache = getattr(session, "result_cache", None)
    if cache is None:
        return []
    return cache.snapshot_rows()


def _device_memory_rows(session) -> list[dict]:
    from .profile import DEVICE_MEM
    rows = [{"metric": "live", "bytes": DEVICE_MEM.live},
            {"metric": "peak", "bytes": DEVICE_MEM.peak},
            {"metric": "window_peak", "bytes": DEVICE_MEM.window_peak()}]
    budget_gb = getattr(session.config, "scan_budget_gb", 0) \
        if session is not None else 0
    if budget_gb and budget_gb > 0:
        budget = int(budget_gb * (1 << 30))
        rows.append({"metric": "budget", "bytes": budget})
        rows.append({"metric": "headroom",
                     "bytes": budget - DEVICE_MEM.peak})
    return rows


_FLIGHT_FIELDS = ("seq", "t_ms", "event", "label", "tenant", "reason",
                  "latency_ms")


def _flight_rows(session) -> list[dict]:
    out = []
    for e in FLIGHT.events():
        row = {k: e.get(k) for k in _FLIGHT_FIELDS}
        extra = {k: v for k, v in e.items() if k not in _FLIGHT_FIELDS}
        row["detail"] = json.dumps(extra, sort_keys=True) if extra else None
        if row["latency_ms"] is not None:
            row["latency_ms"] = float(row["latency_ms"])
        out.append(row)
    return out


def _tables_rows(session) -> list[dict]:
    if session is None:
        return []
    with session._lock:
        names = sorted(session._schemas)
        return [{"name": n,
                 "generation": session._table_generations.get(n, 0),
                 "est_rows": session._est_rows.get(n),
                 "columns": len(session._schemas[n][0]),
                 "unique_cols": ",".join(
                     sorted(session._unique_cols.get(n, ()))) or None}
                for n in names]


def _snapshot_rows(session) -> list[dict]:
    """The attached warehouse's published version log: one row per
    atomic cross-table commit (``tables`` is the ``name@manifest-
    version`` map the version pins; ``current`` marks the published
    head, ``pinned`` the version this session's reads resolve against)."""
    wh = getattr(session, "warehouse", None) if session is not None \
        else None
    if wh is None:
        return []
    cur = wh.current_version()
    pinned = session.warehouse_version()
    return [{"version": rec["version"],
             "timestamp_ms": rec["timestamp_ms"],
             "committer": rec.get("committer") or None,
             "tables": ",".join(
                 f"{t}@{v}" for t, v in sorted(rec["tables"].items())),
             "table_count": len(rec["tables"]),
             "current": rec["version"] == cur,
             "pinned": rec["version"] == pinned}
            for rec in wh.snapshot_records()]


PROVIDERS: dict[str, Callable] = {
    "system.query_log": _query_log_rows,
    "system.metrics": _metrics_rows,
    "system.histograms": _histogram_rows,
    "system.programs": _program_rows,
    "system.result_cache": _result_cache_rows,
    "system.device_memory": _device_memory_rows,
    "system.flight": _flight_rows,
    "system.tables": _tables_rows,
    "system.snapshots": _snapshot_rows,
}


def snapshot_arrow(name: str, session=None) -> pa.Table:
    """One system table's current state as in-memory Arrow (the frozen
    schema, rows cut atomically from the owning registry)."""
    if name not in SYSTEM_SCHEMAS:
        raise KeyError(f"unknown system table {name!r} "
                       f"(have: {', '.join(SYSTEM_SCHEMAS)})")
    return _arrow(name, PROVIDERS[name](session))


def snapshot_engine_table(name: str, session=None):
    """Engine-Table view of :func:`snapshot_arrow` (the host executor's
    scan input)."""
    from ..engine import arrow_bridge
    return arrow_bridge.from_arrow(snapshot_arrow(name, session),
                                   counted=False)


def collect_table_refs(ast) -> set:
    """Every table name referenced anywhere in a parsed statement
    (FROM refs under subqueries/CTEs included) — the routing decision
    input: all-system -> host introspection path, none -> normal path,
    mixed -> typed error."""
    from ..sql import ast_nodes as A
    names: set = set()
    ctes: set = set()
    seen: set = set()

    def walk(x):
        if id(x) in seen or x is None:
            return
        seen.add(id(x))
        if isinstance(x, A.TableRef):
            names.add(x.name)
        if isinstance(x, A.Query):
            ctes.update(n for n, _q in x.ctes)
        if isinstance(x, (list, tuple)):
            for item in x:
                walk(item)
            return
        if hasattr(x, "__dict__"):
            for v in vars(x).values():
                walk(v)
        elif hasattr(x, "__slots__"):
            for s in x.__slots__:
                walk(getattr(x, s, None))
    walk(ast)
    return names - ctes        # CTE aliases are not catalog tables
