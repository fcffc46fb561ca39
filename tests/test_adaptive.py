"""Adaptive execution (ISSUE 17; restated by ISSUE 29): the feedback
stats store closes the loop from observed actuals back into plans.

Since ISSUE 29 a streamed statement's SECOND sighting is sized from what
its first whole pass saw whether or not ``adaptive_plans`` is on
(``Session._stream_group``; tests/test_streaming.py holds that). What the
option adds, and what this file holds, is the store: a profile that
outlives the stream-plan cache entry, so that a FIRST sighting (after a
re-registration, in a second process) starts tight, and its drift
sentinel.

Acceptance-backed properties — all COUNT-shaped or bit-identity (no wall
budgets: this host is 1-core and timing tests flake):

- **low-cardinality right-sizing**: the first sighting of a streamed
  grouped aggregate (``Q`` groups by the 5-value ``k``) provisions every
  capacity decision at the morsel bucket; the second sighting re-records
  from the stored profile and provisions the minimal ladder bucket
  instead — with the response hash-identical across every sighting
  (right-sizing is provisioning, never results);
- **ceiling hint, never a correctness input**: a profile observed on
  small data replayed against grown data overflows the adapted schedule,
  raises ReplayMismatch internally, re-records eagerly, and still
  answers exactly (oracle differential) — counting adaptive_replans;
- **drift sentinel**: when observed actuals collapse below the stored
  profile by the drift ratio, the store refreshes and bumps the
  template generation so cached streamed state re-plans;
- **log<->store equivalence**: replaying a saved query-log JSONL through
  FeedbackStore.replay_log yields the same per-node actuals the live
  session observed (the PR 15 ring<->JSONL property, one layer up);
- **off is off**: adaptive_plans=False (the default) builds no store
  and moves feedback_hits / feedback_refreshes / adaptive_replans by
  exactly zero on a streamed workload, while its second sighting is
  tight all the same; on, the store is fed the very rows that sized it;
- **crash-consistent persistence**: the store round-trips through its
  atomic JSON document at session attach — a fresh session's first
  sighting starts tight from it — and an unreadable document degrades
  to an empty store instead of refusing to start;
- **system.plan_feedback** serves the store's facts over plain SQL.
"""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.engine.arrow_bridge import to_arrow
from nds_tpu.engine.feedback import FeedbackStore
from nds_tpu.engine.streaming import adapt_schedule, inflate_schedule
from nds_tpu.obs.metrics import (ADAPTIVE_REPLANS, FEEDBACK_HITS,
                                 FEEDBACK_REFRESHES, TIGHT_MORSEL_REPLAYS)
from nds_tpu.obs.query_log import QUERY_LOG, read_jsonl

Q = "SELECT k, SUM(v) AS sv FROM big GROUP BY k ORDER BY k"


@pytest.fixture(autouse=True)
def _log_off():
    QUERY_LOG.configure(enabled=False, capacity=4096, path="", clear=True)
    yield
    QUERY_LOG.configure(enabled=False, capacity=4096, path="", clear=True)


def counters():
    return (FEEDBACK_HITS.value, FEEDBACK_REFRESHES.value,
            ADAPTIVE_REPLANS.value)


def make_session(**over) -> Session:
    cfg = dict(use_jax=True, out_of_core=True, out_of_core_min_rows=1000,
               chunk_rows=4096)
    cfg.update(over)
    return Session(EngineConfig(**cfg))


def low_card(n=20000, lo=0, hi=5, seed=0) -> pa.Table:
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(lo, hi, n),
                     "v": rng.integers(0, 100, n)})


def arrow_rows(table):
    return to_arrow(table).to_pylist()


def cap_cells(store: FeedbackStore, template: str, table: str) -> list:
    """Observed cap values of the stored group profile."""
    with store._lock:
        g = store._templates[template]["groups"][table]
        return [[c for c, k in zip(cs, ks) if k == "cap"]
                for cs, ks in zip(g["caps"], g["kinds"])]


# -- schedule adaptation unit ------------------------------------------------

def test_adapt_schedule_falls_back_and_clamps():
    dec = [("exact", 3), ("cap", 7), ("cap", 2)]
    # no observations / structural drift -> plain morsel inflation
    assert adapt_schedule(dec, 4096, None) == inflate_schedule(dec, 4096)
    assert adapt_schedule(dec, 4096, [3, 7]) == inflate_schedule(dec, 4096)
    # observed maxima replace the morsel bound, record actual still floors
    adapted = adapt_schedule(dec, 4096, [3, 100, 1])
    assert adapted == [("exact", 3), ("cap", 100), ("cap", 2)]


def test_member_caps_requires_structural_match():
    fb = FeedbackStore()
    fb.observe_group("t", "big", bound=4096, fused=False, shards=0,
                     kinds=[["exact", "cap"]], caps=[[3, 9]])
    ok = fb.member_caps("t", "big", 0, ["exact", "cap"], 4096, False, 0)
    assert ok == [3, 9]
    assert fb.member_caps("t", "big", 0, ["cap", "cap"], 4096,
                          False, 0) is None          # kinds drift
    assert fb.member_caps("t", "big", 0, ["exact", "cap"], 8192,
                          False, 0) is None          # bound drift
    assert fb.member_caps("t", "big", 0, ["exact", "cap"], 4096,
                          True, 0) is None           # fusion drift
    assert fb.member_caps("t", "big", 1, ["exact", "cap"], 4096,
                          False, 0) is None          # no such member


# -- the low-cardinality right-size -------------------------------------------

def test_second_sighting_rightsizes_caps_bit_identically():
    """First sighting provisions the morsel bucket; the second re-records
    from observed actuals and drops every group-by capacity to the
    minimal ladder bucket — responses hash-identical throughout."""
    s = make_session(adaptive_plans=True)
    s.register_arrow("big", low_card())
    h0, _r0, a0 = counters()
    ref = arrow_rows(s.sql(Q, label="lowcard"))
    assert FEEDBACK_HITS.value == h0          # nothing to consume yet
    assert s._feedback.stamp("lowcard") > 0     # ...but it observed
    out2 = arrow_rows(s.sql(Q, label="lowcard"))
    assert FEEDBACK_HITS.value == h0 + 1      # profile consumed
    assert ADAPTIVE_REPLANS.value == a0 + 1   # stamp-driven re-plan
    out3 = arrow_rows(s.sql(Q, label="lowcard"))  # steady state: replay
    assert FEEDBACK_HITS.value == h0 + 1
    assert out2 == ref and out3 == ref
    # the observed profile needs the MINIMAL bucket, not the morsel one
    cells = cap_cells(s._feedback, "lowcard", "big")
    assert all(c <= 8 for row in cells for c in row)
    applied = s._feedback.applied["lowcard"]
    assert applied["cap_cells_after"] * 100 <= applied["cap_cells_before"]


def test_observed_estimates_override_catalog(tmp_path):
    """The catalog prefers the store's observed table rows over the
    registered static estimate on the next sighting of the template."""
    s = make_session(adaptive_plans=True)
    s.register_arrow("big", low_card())
    assert s._est_rows_for("big", 0, "t") == 20000   # registered estimate
    s.sql(Q, label="t")
    # the streamed pass observed the exact row count; same answer here,
    # but through the feedback store now
    assert s._feedback.table_rows("t")["big"] == 20000
    assert s._est_rows_for("big", 0, "t") == 20000
    # a label that never streamed keeps the static estimate
    assert s._est_rows_for("big", 0, "other") == 20000


# -- ceiling hint: under-observation re-records, never mis-answers -----------

def test_underobserved_hint_rerecords_and_stays_exact():
    """A profile observed on low-cardinality data replayed against grown
    data overflows the adapted schedule mid-stream; the engine re-records
    eagerly (adaptive_replans moves) and the answer stays exact."""
    s = make_session(adaptive_plans=True)
    s.register_arrow("big", low_card())
    for _ in range(2):
        s.sql(Q, label="grow")        # observe + adapt on low-card data
    assert all(c <= 8 for row in cap_cells(s._feedback, "grow", "big")
               for c in row)
    # grown data: morsel 1 keeps the low cardinality (so the record pass
    # cannot see what is coming), morsel 2+ explodes the group count past
    # the adapted ceiling
    rng = np.random.default_rng(1)
    k = np.concatenate([rng.integers(0, 5, 4096),
                        rng.integers(0, 3000, 8192)])
    v = rng.integers(0, 100, k.size)
    grown = pa.table({"k": k, "v": v})
    s.register_arrow("big", grown)    # generation bump clears stream cache
    a0 = ADAPTIVE_REPLANS.value
    out = arrow_rows(s.sql(Q, label="grow"))
    assert ADAPTIVE_REPLANS.value > a0         # overflow -> eager re-record
    oracle = make_session()
    oracle.register_arrow("big", grown)
    assert out == arrow_rows(oracle.sql(Q, backend="numpy", label="grow"))
    # ...and the store now provisions for what was actually seen
    assert any(c > 8 for row in cap_cells(s._feedback, "grow", "big")
               for c in row)


def test_drift_sentinel_refreshes_stale_profile():
    """Observed actuals collapsing below the stored profile by the drift
    ratio refresh the profile (feedback_refreshes) and bump the template
    generation, so the next sighting re-plans down."""
    s = make_session(adaptive_plans=True, feedback_drift_ratio=4.0)
    rng = np.random.default_rng(2)
    s.register_arrow("big", pa.table({
        "k": rng.integers(0, 3000, 12288),
        "v": rng.integers(0, 100, 12288)}))
    for _ in range(2):
        s.sql(Q, label="shrink")      # profile at high cardinality
    assert any(c > 1000 for row in cap_cells(s._feedback, "shrink", "big")
               for c in row)
    r0 = FEEDBACK_REFRESHES.value
    s.register_arrow("big", low_card(n=12288))
    gen_before = s._feedback.stamp("shrink")
    ref = arrow_rows(s.sql(Q, label="shrink"))
    assert FEEDBACK_REFRESHES.value > r0       # sentinel fired
    assert s._feedback.stamp("shrink") > gen_before
    out = arrow_rows(s.sql(Q, label="shrink"))  # re-plans from fresh profile
    assert out == ref
    assert all(c <= 8 for row in cap_cells(s._feedback, "shrink", "big")
               for c in row)


# -- off is off ---------------------------------------------------------------

def group_caps(s: Session) -> list:
    """Cap values of the morsel programs the session holds for Q."""
    (state,) = s._stream_cache[Q]["gstates"]
    return [[v for k, v in cq.decisions if k == "cap"]
            for cq in state["cqs"]]


def test_disabled_mode_builds_no_store_and_moves_no_counters():
    """Off: no store, the three feedback counters strictly still — and the
    second sighting tight all the same (ISSUE 29: sized from the first
    whole pass, which needs no store)."""
    before = counters()
    t0 = TIGHT_MORSEL_REPLAYS.value
    s = make_session()                # adaptive_plans defaults False
    s.register_arrow("big", low_card())
    ref = arrow_rows(s.sql(Q, label="off"))
    assert TIGHT_MORSEL_REPLAYS.value == t0
    assert all(c <= 8 for row in group_caps(s) for c in row)
    assert arrow_rows(s.sql(Q, label="off")) == ref
    assert TIGHT_MORSEL_REPLAYS.value == t0 + s.last_exec_stats["morsels"]
    assert s.last_exec_stats.get("re_records", 0) == 0
    assert s._feedback is None
    assert counters() == before
    assert "decision_rows" not in s.last_exec_stats.get("extra", {})


def test_the_store_is_fed_the_rows_that_size_the_second_sighting():
    """On and off observe alike: the profile the store keeps after one
    sighting is, cap for cap, what the off session's tight programs hold."""
    off = make_session()
    off.register_arrow("big", low_card())
    off.sql(Q, label="same")
    on = make_session(adaptive_plans=True)
    on.register_arrow("big", low_card())
    on.sql(Q, label="same")
    assert cap_cells(on._feedback, "same", "big") == group_caps(off)
    (state,) = on._stream_cache[Q]["gstates"]
    assert cap_cells(on._feedback, "same", "big") == \
        [[c for c, k in zip(row, ks) if k == "cap"]
         for row, ks in zip(state["obs"], state["kinds"])]


# -- log <-> store equivalence ------------------------------------------------

def test_query_log_replay_reconstructs_live_observations(tmp_path):
    """The query log's node_stats column replayed through replay_log
    yields the SAME per-node actuals the live session observed."""
    ql = str(tmp_path / "qlog.jsonl")
    s = make_session(adaptive_plans=True, query_log=True,
                     query_log_path=ql)
    s.register_arrow("big", low_card())
    for label in ("qa", "qb"):
        for _ in range(3):
            s.sql(Q, label=label)
    QUERY_LOG.flush()
    rows = read_jsonl(ql)
    assert any(r.get("node_stats") for r in rows)
    offline = FeedbackStore()
    assert offline.replay_log(rows) > 0
    for label in ("qa", "qb"):
        live = s._feedback.node_rows(label)
        assert live and offline.node_rows(label) == live
    # ring rows replay identically to file rows (they are the same rows)
    ring = FeedbackStore()
    ring.replay_log(QUERY_LOG.rows())
    assert ring.node_rows("qa") == offline.node_rows("qa")


# -- persistence --------------------------------------------------------------

def test_store_roundtrips_at_attach_and_fails_soft(tmp_path):
    fbp = str(tmp_path / "plan_feedback.json")
    s = make_session(adaptive_plans=True, feedback_path=fbp)
    s.register_arrow("big", low_card())
    for _ in range(2):
        s.sql(Q, label="persist")
    s._feedback.flush()
    doc = json.load(open(fbp))
    assert doc["version"] == 1 and "persist" in doc["templates"]
    # a fresh session warm-starts: the FIRST sighting already adapts —
    # what the store still adds now that a second sighting is tight anyway
    h0 = FEEDBACK_HITS.value
    s2 = make_session(adaptive_plans=True, feedback_path=fbp)
    s2.register_arrow("big", low_card())
    ref = arrow_rows(s.sql(Q, label="persist"))
    assert arrow_rows(s2.sql(Q, label="persist")) == ref
    assert FEEDBACK_HITS.value > h0
    assert all(c <= 8 for row in group_caps(s2) for c in row)
    (state2,) = s2._stream_cache[Q]["gstates"]
    assert state2["adapted"] and not state2["tight"]   # nothing left to gain
    # derived placement: beside the query log when only that is set
    ql = str(tmp_path / "logs" / "q.jsonl")
    s3 = make_session(adaptive_plans=True, query_log=True,
                      query_log_path=ql)
    assert s3._feedback.path == str(tmp_path / "logs" /
                                    "plan_feedback.json")
    # unreadable document: advisory store starts empty, engine still runs
    with open(fbp, "w") as f:
        f.write("{corrupt")
    s4 = make_session(adaptive_plans=True, feedback_path=fbp)
    s4.register_arrow("big", low_card())
    assert s4._feedback.stamp("persist") == 0
    assert arrow_rows(s4.sql(Q, label="persist")) == ref


# -- system.plan_feedback -----------------------------------------------------

def test_plan_feedback_table_serves_store_facts():
    s = make_session(adaptive_plans=True)
    s.register_arrow("big", low_card())
    for _ in range(2):
        s.sql(Q, label="sysq")
    rows = arrow_rows(s.sql(
        "SELECT template, kind, node, rows FROM system.plan_feedback "
        "ORDER BY kind, node"))
    kinds = {r["kind"] for r in rows}
    assert {"node", "table", "cap"} <= kinds
    by_kind = {k: [r for r in rows if r["kind"] == k] for k in kinds}
    assert any(r["rows"] == 20000 for r in by_kind["table"])
    assert all(r["template"] == "sysq" for r in rows)
    # adaptive off: the table exists and is empty
    s2 = make_session()
    s2.register_arrow("big", low_card())
    assert arrow_rows(s2.sql(
        "SELECT template FROM system.plan_feedback")) == []
