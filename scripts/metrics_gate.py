#!/usr/bin/env python
"""CI metrics gate: diff a deterministic workload's counter deltas
against a checked-in baseline.

Timing on this class of host flakes (a loaded 1-core runner can double
any wall number), so CI cannot gate on milliseconds — but it CAN gate on
COUNT-shaped metrics, which are functions of the engine's decisions, not
of the scheduler: how many programs compiled, how many cache hits served
replays, how many morsels streamed, how many queries a batched dispatch
absorbed. A regression that breaks a cache key, defeats batching, or
re-traces every morsel moves these counts by integer factors while every
test still passes bit-identical — exactly the failure class PR 9 found
by hand (the PackedTable aux-hash bug re-traced EVERY morsel; compiles
would have exploded in this gate).

Mechanics:

1. run a fixed synthetic workload (in-core record/compile/replay x3,
   a streamed low-cardinality scan x2, and a held 4-ticket service batch)
   on a fresh in-process engine;
2. take the registry counter snapshot; keep COUNT-shaped metrics only —
   ``*_ms`` wall metrics and ``*_bytes``-free size metrics are
   REPORT-ONLY (printed, never gated);
3. diff against ``cicd/metrics_baseline.json``: strict-zero metrics
   (replay_mismatches, host_fallbacks, ...) must stay exactly 0; every
   other gated counter passes within a generous ratio band
   (x0.5 .. x2.0, or an absolute slack of +-2 for small counts);
4. exit nonzero on any violation, printing the offending rows.

Refresh the baseline after an intentional behavior change:

  python scripts/metrics_gate.py --update
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASELINE = os.path.join(REPO, "cicd", "metrics_baseline.json")

#: metrics that must be EXACTLY ZERO on the gate workload: any movement
#: is a behavior regression (a replay invalidated, an operator falling
#: back to host, a staging thread failing), never noise
STRICT_ZERO = (
    "replay_mismatches", "morsel_re_records", "host_fallbacks",
    "query_failures", "prefetch_errors", "fault_point_firings",
    "service_rejected", "service_deadline_expired", "stream_restarts",
    # chaos-hardened serving: a CLEAN workload must never trip a breaker
    # or quarantine a program — movement here means the self-healing
    # machinery fired on healthy traffic
    "circuit_trips", "quarantined_programs",
    # semantic result cache: the gate workload runs with the cache OFF,
    # so a hit here means some layer armed it (or served a cached
    # result) without being asked — a behavior regression, never noise
    "result_cache_hits",
    # EXPLAIN ANALYZE: the gate workload runs with profiling OFF, so any
    # profiled query, audit finding, or histogram-series fold here means
    # the disabled path grew profiling work (the zero-cost contract)
    "profiled_queries", "cardinality_misestimates",
    "histogram_series_overflow",
    # system tables + durable query log: the gate workload runs with the
    # log DISABLED and issues no system.* statement, so any row, file
    # rotation, or served introspection query here means the disabled
    # path grew work (one branch per statement is the whole budget)
    "system_queries", "query_log_rows", "query_log_rotations",
    # transactional warehouse: the gate workload is query-only (no
    # warehouse attached, no DML), so a commit, rollback, or recovery
    # sweep here means the read path started opening transactions — the
    # pinning-disabled/bit-identical contract broke
    "txn_commits", "txn_rollbacks", "txn_recoveries",
    # distributed serving front door: the gate workload is in-process
    # (no FrontDoorServer, fair_queue/preemption/inflight_dedup all at
    # their off defaults), so any wire request, preemption, dedup share,
    # cache snapshot export, or client-side cache hit here means the
    # disabled path grew serving work — the bit-identical off contract
    "frontdoor_requests", "frontdoor_errors", "service_preemptions",
    "service_inflight_dedup", "result_cache_snapshots",
    "frontdoor_client_cache_hits",
    # the gate workload's one join is a star join (a unique integer key
    # whose span fits the lookup table): direct_joins counts it per
    # dispatch, in the band like any count; one on the sort-based path
    # means the direct-address join's eligibility broke
    "sorted_joins",
)

#: report-only name suffixes: wall-clock and byte-volume metrics flake
#: with host load / layout evolution — printed for the log, never gated.
#: tight_morsel_replays counts how often a streamed statement was seen
#: again, which is the workload's choice and no behaviour of the engine;
#: mask_carried_filters counts dispatches of programs whose plan holds a
#: filter under a keyless integer aggregate, the workload's choice too;
#: bytes_decoded is a byte volume like bytes_uploaded (it moves with the
#: Arrow layout of the source's batches)
REPORT_ONLY_SUFFIXES = ("_ms", "_bytes", "bytes_uploaded", "bytes_fetched",
                        "tight_morsel_replays", "mask_carried_filters",
                        "bytes_decoded")

RATIO_LO, RATIO_HI = 0.5, 2.0
ABS_SLACK = 2


def run_workload() -> dict:
    """The fixed workload; returns the registry snapshot AFTER it.

    Deterministic by construction: fixed rng seeds, fixed query texts,
    and the service batch accumulates under hold_dispatch so batching
    does not depend on thread timing."""
    import time

    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from nds_tpu.config import EngineConfig
    from nds_tpu.engine import Session
    from nds_tpu.obs.metrics import METRICS
    from nds_tpu.service import QueryService, ServiceConfig

    import tempfile

    rng = np.random.default_rng(41)
    n_fact, n_dim = 20_000, 50
    fact = pa.table({
        "fk": pa.array(rng.integers(0, n_dim, n_fact), type=pa.int64()),
        "qty": pa.array(rng.integers(1, 100, n_fact), type=pa.int64()),
    })
    dim = pa.table({"dk": pa.array(np.arange(n_dim), type=pa.int64()),
                    "grp": pa.array((np.arange(n_dim) % 7)
                                    .astype(np.int64))})

    # 1. in-core record -> compile+run -> compiled replay
    s = Session(EngineConfig())
    s.register_arrow("fact", fact)
    s.register_arrow("dim", dim)
    tpl = ("SELECT grp, COUNT(*) AS n, SUM(qty) AS tq FROM fact "
           "JOIN dim ON fk = dk WHERE qty BETWEEN {a} AND {b} "
           "GROUP BY grp ORDER BY grp")
    for _ in range(3):
        s.sql(tpl.format(a=5, b=60), label="gate_incore")

    # 2. streamed morsel scan (low-cardinality column: the encoded path
    #    participates, so decode/dict counters gate too)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "sfact.parquet")
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 9, 60_000), type=pa.int32()),
            "v": pa.array(rng.integers(0, 1000, 60_000), type=pa.int64()),
        }), path, row_group_size=8192)
        s2 = Session(EngineConfig(chunk_rows=8192,
                                  out_of_core_min_rows=10_000))
        s2.register_parquet("sfact", path)
        for _ in range(2):
            s2.sql("SELECT k, COUNT(*) AS n, SUM(v) AS sv FROM sfact "
                   "GROUP BY k ORDER BY k", label="gate_stream")

    # 3. service: warm then one held batch of 4 compatible tickets
    with QueryService(s, ServiceConfig(max_batch=8)) as svc:
        svc.sql(tpl.format(a=5, b=60), label="gate_warm")
        svc.sql(tpl.format(a=5, b=60), label="gate_warm")
        with svc.hold_dispatch():
            tickets = [svc.submit(tpl.format(a=5 + i, b=60 + i),
                                  label=f"gate_b{i}", tenant="gate")
                       for i in range(4)]
            t0 = time.time()
            while time.time() - t0 < 30:
                with svc._cv:
                    if len(svc._ready) >= len(tickets):
                        break
                time.sleep(0.005)
        for t in tickets:
            t.result(timeout=120)
    return METRICS.snapshot()


def gated_view(snapshot: dict) -> tuple[dict, dict]:
    """(gated, report_only) split of a snapshot."""
    gated, report = {}, {}
    for name, v in snapshot.items():
        if any(name.endswith(sfx) for sfx in REPORT_ONLY_SUFFIXES):
            report[name] = v
        else:
            gated[name] = v
    return gated, report


def compare(baseline: dict, now: dict) -> list[str]:
    """Violation messages (empty = gate passes)."""
    out = []
    for name in STRICT_ZERO:
        if now.get(name, 0) != 0:
            out.append(f"STRICT-ZERO {name}: {now[name]} (must be 0)")
    for name, base in sorted(baseline.items()):
        if name in STRICT_ZERO:
            continue
        cur = now.get(name)
        if cur is None:
            out.append(f"MISSING {name}: baseline {base}, not in snapshot")
            continue
        if abs(cur - base) <= ABS_SLACK:
            continue
        if base > 0 and RATIO_LO <= cur / base <= RATIO_HI:
            continue
        out.append(f"OUT-OF-BAND {name}: {cur} vs baseline {base} "
                   f"(band x{RATIO_LO}-x{RATIO_HI}, slack +-{ABS_SLACK})")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="metrics_gate.py", description=(
        "run the deterministic gate workload and diff count-shaped "
        "engine counters against the checked-in baseline"))
    p.add_argument("--baseline", default=BASELINE)
    p.add_argument("--update", action="store_true",
                   help="write the current counts as the new baseline "
                        "instead of gating")
    a = p.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    snapshot = run_workload()
    gated, report = gated_view(snapshot)
    if a.update:
        os.makedirs(os.path.dirname(a.baseline), exist_ok=True)
        with open(a.baseline, "w") as f:
            json.dump({"workload_version": 1, "gated": gated,
                       "report_only": report}, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"metrics_gate: baseline updated -> {a.baseline}")
        return 0
    try:
        with open(a.baseline) as f:
            doc = json.load(f)
    except OSError as e:
        print(f"metrics_gate: no baseline ({e}); run with --update first",
              file=sys.stderr)
        return 2
    violations = compare(doc["gated"], gated)
    print(json.dumps({"gated": gated, "report_only": report,
                      "violations": violations}, sort_keys=True))
    if violations:
        for v in violations:
            print(f"metrics_gate: {v}", file=sys.stderr)
        print(f"metrics_gate: FAIL ({len(violations)} violations)",
              file=sys.stderr)
        return 1
    print(f"metrics_gate: OK ({len(doc['gated'])} baseline metrics, "
          f"{len(gated)} observed)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
