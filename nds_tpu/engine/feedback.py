"""Feedback stats store: observed actuals close the loop back to plans.

Every run already measures itself exactly — ``ExecStats.node_stats``
records per-node actual row counts under the verifier's stable
``TypeName#k`` labels, the streamed morsel path host-fetches one check
scalar per capacity decision on every replay, and the durable query log
persists all of it. This module is the part that ACTS on what the engine
sees (ROADMAP item 2, the history-based optimization "Accelerating
Presto with GPUs" treats as table stakes): a per-template store of
observed cardinalities that the NEXT sighting of the same template
consumes.

Three observation surfaces, one store:

- **nodes** — ``{TypeName#k: max rows}`` per template, fed from
  ``Session._finish_exec_stats`` (and therefore the service ticket path,
  which lands there too). Reconstructable OFFLINE from a query-log JSONL
  via :meth:`FeedbackStore.replay_log` — the log's ``node_stats`` column
  carries the same map, and replaying it yields the same per-node
  actuals the live session recorded (a tested property).
- **tables** — exact rows streamed per big table per template: the
  planner's catalog prefers these over the registered static
  ``est_rows`` on the next sighting (``Session._est_rows_for``), so a
  mis-registered estimate flips streamed-vs-in-core and
  late-materialization decisions back to what the data actually is.
- **groups** — per-decision observed MAXIMA of each streamed scan
  group's capacity schedule, merged across every morsel of every
  sighting (record-pass actuals + replay check scalars: the rows
  ``Session._stream_group`` collects on every streamed pass, store or
  no store). A FIRST sighting that finds a structurally matching profile
  here right-sizes its capacity-ladder buckets from it instead of
  inflating every cap to the morsel bound (``streaming.adapt_schedule``)
  — a low-cardinality GROUP BY drops from the morsel bucket to the
  minimal ladder bucket (a keyless aggregate records no cap to adapt:
  its one group is static). A repeated sighting within one stream-plan
  cache entry needs no store for that: the session sizes it from the
  entry's own first whole pass. What this surface adds is the profile
  that outlives the entry (a re-registration, a second process) and the
  drift sentinel below.

Discipline (the house default-off contract):

- An observed cap is a **ceiling hint**, never a correctness input: an
  under-observed actual overflows the adapted schedule's check at
  replay, raises ``ReplayMismatch``, and the morsel re-records eagerly —
  exactly the machinery morsel-bound inflation already relies on. A
  stale profile can cost a re-record; it can never mis-answer.
- **Drift sentinel**: when a template's observed profile diverges from
  its own history past ``drift_ratio`` (on the bucket scale, either
  direction), the store refreshes the history and bumps the template
  generation, so the next sighting re-records instead of replaying a
  stale schedule (``feedback_refreshes``; stamp-driven re-records count
  ``adaptive_replans``).
- ``EngineConfig.adaptive_plans=False`` (the default) never constructs a
  store: the three feedback counters stay strictly zero.

Persistence is one crash-consistent JSON document beside the query log,
written with the warehouse's atomic-rename discipline
(``warehouse._atomic_write_json``: temp file -> fsync -> rename ->
directory fsync) and loaded at session attach. The store is advisory, so
an unreadable document degrades to an empty store with a warning — the
engine re-observes; it never refuses to start over a hint file.
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Optional

from ..obs import metrics as _metrics
from ..obs.flight import FLIGHT

log = logging.getLogger(__name__)

#: observations between automatic flushes of the JSON document (a flush
#: is two fsyncs — the same price as one warehouse manifest commit — so
#: per-statement flushing would tax the hot path; close/bench flush
#: explicitly)
FLUSH_EVERY = 16

DOC_VERSION = 1


def _bucket(n: int) -> int:
    from .jax_backend.device import bucket
    return bucket(max(int(n), 1))


def _new_template() -> dict:
    return {"sightings": 0, "refreshes": 0, "gen": 0, "updated": 0.0,
            "nodes": {}, "tables": {}, "groups": {}}


class FeedbackStore:
    """Per-template observed-cardinality store (one per adaptive session).

    Thread-safe: observations land under the session statement lock, but
    ``system.plan_feedback`` snapshots and the service's planner threads
    read concurrently, so every accessor cuts under the store's own lock.
    """

    def __init__(self, path: Optional[str] = None,
                 drift_ratio: float = 4.0) -> None:
        self.path = path
        self.drift_ratio = max(float(drift_ratio), 1.0)
        self._lock = threading.Lock()
        self._templates: dict[str, dict] = {}
        #: per-template last-applied right-sizing summary (bench's
        #: "adaptive" block): capacity cells the morsel-bound inflation
        #: would have provisioned vs what the adapted schedule did
        self.applied: dict[str, dict] = {}
        self._dirty = 0
        if path and os.path.exists(path):
            self._load(path)

    # -- persistence ---------------------------------------------------------
    def _load(self, path: str) -> None:
        try:
            with open(path) as f:
                doc = json.load(f)
            if doc.get("version") != DOC_VERSION:
                raise ValueError(f"unknown version {doc.get('version')!r}")
            self._templates = doc.get("templates", {})
        except (OSError, ValueError) as e:
            # advisory store: a bad hint file must not block the engine —
            # start empty and re-observe (the next flush rewrites it)
            log.warning("feedback store %s unreadable (%s); starting empty",
                        path, e)
            self._templates = {}

    def flush(self) -> None:
        """Write the document crash-consistently (atomic rename + dir
        fsync, the warehouse manifest discipline). No-op without a path."""
        if not self.path:
            return
        from ..warehouse import _atomic_write_json
        with self._lock:
            doc = {"version": DOC_VERSION,
                   "templates": json.loads(json.dumps(self._templates))}
            self._dirty = 0
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        _atomic_write_json(self.path, doc)

    def _note_dirty_locked(self) -> bool:
        self._dirty += 1
        return bool(self.path) and self._dirty >= FLUSH_EVERY

    # -- observation ---------------------------------------------------------
    def observe_nodes(self, template: str,
                      node_stats: Optional[dict]) -> None:
        """One completed statement's per-node actuals (TypeName#k -> rows).
        Max-merge against history; a bucket-scale downward divergence past
        drift_ratio refreshes the stored value instead (stale history)."""
        if not template or not node_stats:
            return
        flush = False
        with self._lock:
            t = self._templates.setdefault(template, _new_template())
            t["sightings"] += 1
            t["updated"] = round(time.time(), 3)
            nodes = t["nodes"]
            refreshed = False
            for lbl, rows in node_stats.items():
                rows = int(rows)
                old = nodes.get(lbl)
                if old is None or rows > old:
                    nodes[lbl] = rows
                elif _bucket(old) >= self.drift_ratio * _bucket(rows):
                    nodes[lbl] = rows       # history is stale: refresh down
                    refreshed = True
            if refreshed:
                t["refreshes"] += 1
            flush = self._note_dirty_locked()
        if refreshed:
            _metrics.FEEDBACK_REFRESHES.inc()
            FLIGHT.record("feedback_refresh", label=template, kind="nodes")
        if flush:
            self.flush()

    def observe_tables(self, template: str, rows_by_table: dict) -> None:
        """Exact rows streamed per big table this sighting. Stored as the
        LATEST observation (a full scan is ground truth, not a lower
        bound); a bucket-scale change bumps the template generation so
        cached streamed state re-plans against the corrected estimate."""
        if not template or not rows_by_table:
            return
        flush = False
        bumped = False
        with self._lock:
            t = self._templates.setdefault(template, _new_template())
            for name, rows in rows_by_table.items():
                rows = int(rows)
                old = t["tables"].get(name)
                t["tables"][name] = rows
                if old is None or _bucket(old) != _bucket(rows):
                    bumped = True
            if bumped:
                t["gen"] += 1
            flush = self._note_dirty_locked()
        if flush:
            self.flush()

    def observe_group(self, template: str, table: str, bound: int,
                      fused: bool, shards: int, kinds: list,
                      caps: list) -> None:
        """One streamed scan group's per-decision observed maxima (one row
        per member program; fused groups have a single shared schedule).
        Structure mismatch (different kinds/bound/fusion/sharding)
        replaces the profile; growth max-merges; a bucket-scale downward
        divergence past drift_ratio on any cap refreshes the profile —
        each of those bumps the generation, so the stream cache's stamp
        check re-records the template instead of replaying stale caps."""
        if not template:
            return
        kinds_l = [list(k) for k in kinds]
        caps_l = [[int(c) for c in row] for row in caps]
        refreshed = False
        flush = False
        with self._lock:
            t = self._templates.setdefault(template, _new_template())
            g = t["groups"].get(table)
            if g is None or g["kinds"] != kinds_l or g["bound"] != bound \
                    or g["fused"] != fused or g["shards"] != shards \
                    or [len(r) for r in g["caps"]] != \
                    [len(r) for r in caps_l]:
                t["groups"][table] = {
                    "bound": int(bound), "fused": bool(fused),
                    "shards": int(shards), "kinds": kinds_l, "caps": caps_l}
                t["gen"] += 1
            else:
                bumped = False
                for stored, seen, ks in zip(g["caps"], caps_l, kinds_l):
                    for i, k in enumerate(ks):
                        if k != "cap":
                            continue
                        if seen[i] > stored[i]:
                            if _bucket(seen[i]) != _bucket(stored[i]):
                                bumped = True
                            stored[i] = seen[i]
                        elif _bucket(stored[i]) >= \
                                self.drift_ratio * _bucket(seen[i]):
                            refreshed = True
                if refreshed:
                    # stale history: replace wholesale with this run's
                    # faithful profile rather than keeping inflated maxima
                    g["caps"] = caps_l
                    t["refreshes"] += 1
                    bumped = True
                if bumped:
                    t["gen"] += 1
            t["updated"] = round(time.time(), 3)
            flush = self._note_dirty_locked()
        if refreshed:
            _metrics.FEEDBACK_REFRESHES.inc()
            FLIGHT.record("feedback_refresh", label=template, table=table,
                          kind="schedule")
        if flush:
            self.flush()

    # -- consumption ---------------------------------------------------------
    def stamp(self, template: str) -> int:
        """The template's profile generation: cached streamed state
        records the stamp it was built under, and a moved stamp means
        observations changed enough to warrant a re-record."""
        with self._lock:
            t = self._templates.get(template)
            return t["gen"] if t is not None else 0

    def node_rows(self, template: str) -> dict:
        with self._lock:
            t = self._templates.get(template)
            return dict(t["nodes"]) if t is not None else {}

    def table_rows(self, template: str) -> dict:
        with self._lock:
            t = self._templates.get(template)
            return dict(t["tables"]) if t is not None else {}

    def member_caps(self, template: str, table: str, member: int,
                    kinds: list, bound: int, fused: bool,
                    shards: int) -> Optional[list]:
        """Observed per-decision maxima for one member program of one
        group, or None when no STRUCTURALLY MATCHING profile exists (the
        recorded kinds sequence, morsel bound, fusion and sharding mode
        must all match — anything else is a different program shape and
        adapting it would be guessing, not feedback)."""
        with self._lock:
            t = self._templates.get(template)
            g = t["groups"].get(table) if t is not None else None
            if g is None or g["bound"] != bound or g["fused"] != fused \
                    or g["shards"] != shards or member >= len(g["caps"]):
                return None
            if g["kinds"][member] != list(kinds):
                return None
            return list(g["caps"][member])

    def note_applied(self, template: str, cells_before: int,
                     cells_after: int) -> None:
        """Record one right-sizing application (bench's "adaptive" block:
        capacity cells the morsel-bound inflation would have provisioned
        vs the adapted schedule)."""
        with self._lock:
            a = self.applied.setdefault(
                template, {"groups": 0, "cap_cells_before": 0,
                           "cap_cells_after": 0})
            a["groups"] += 1
            a["cap_cells_before"] += int(cells_before)
            a["cap_cells_after"] += int(cells_after)

    # -- offline seeding ------------------------------------------------------
    def replay_log(self, rows) -> int:
        """Seed the store from saved query-log rows (read_jsonl / ring
        rows): each row's ``node_stats`` column replays through the SAME
        observe path the live session fed, so offline reconstruction
        yields identical per-node actuals. Returns rows consumed."""
        n = 0
        for r in rows:
            ns = r.get("node_stats")
            if not ns or not r.get("label"):
                continue
            if isinstance(ns, str):
                try:
                    ns = json.loads(ns)
                except ValueError:
                    continue
            self.observe_nodes(r["label"], ns)
            n += 1
        return n

    # -- introspection (system.plan_feedback) ---------------------------------
    def snapshot_rows(self) -> list[dict]:
        """One row per observed fact, under the store lock (the atomic-cut
        contract every system.* provider keeps): kind "node" rows carry
        TypeName#k actuals, kind "table" rows the observed scan rows, and
        kind "cap" rows each schedule decision's observed maximum."""
        out = []
        with self._lock:
            for name, t in sorted(self._templates.items()):
                base = {"template": name, "sightings": t["sightings"],
                        "refreshes": t["refreshes"], "gen": t["gen"]}
                for lbl, rows in sorted(t["nodes"].items()):
                    out.append({**base, "kind": "node", "node": lbl,
                                "table": None, "rows": rows})
                for tab, rows in sorted(t["tables"].items()):
                    out.append({**base, "kind": "table", "node": None,
                                "table": tab, "rows": rows})
                for tab, g in sorted(t["groups"].items()):
                    for mi, (ks, cs) in enumerate(zip(g["kinds"],
                                                      g["caps"])):
                        for di, k in enumerate(ks):
                            if k != "cap":
                                continue
                            out.append({**base, "kind": "cap",
                                        "node": f"m{mi}:d{di}",
                                        "table": tab, "rows": cs[di]})
        return out
