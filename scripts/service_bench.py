#!/usr/bin/env python
"""Open-loop query-service benchmark -> SERVICE_r*.json.

Measures the concurrent query service (nds_tpu/service) the way ROADMAP
item 4 demands it be measured: sustained QPS and tail latency under N
CONCURRENT CLIENTS against the serial one-query-at-a-time baseline on the
same host — not stream-elapsed. The workload is dashboard-shaped
interactive analytics over the SF0.01 NDS warehouse: T parameterized
templates, each with a shared pool of literal instantiations, clients
drawing from the pool (cross-client text repeats and compatible
parameterized plans are the NORM, exactly the shape the shared plan/
program cache and compatible-plan batching exist for).

Phases:
  1. serial baseline — a fresh single-caller Session runs the whole
     workload one query at a time (after per-template warmup), recording
     wall, per-query latency, and a result hash per distinct text;
  2. per clients count C — a fresh Session + QueryService, per-template
     warmup (record + compile + publish), a short surge at concurrency C
     to warm batched program shapes, then the measured window: C client
     threads each submit-and-wait through their query lists. Every
     response hashes against the serial baseline (bit-identity is part of
     the record), latency decomposes into queue_wait + execute via
     ExecStats.queue_wait_ms, and batching shows up as batched_with.

Latency percentiles (p50/p99, queue-wait) come from the REGISTRY
histograms (obs.metrics — the same per-tenant/per-template SLO source a
live operator reads), cut to the measured window via snapshot diffs; a
``percentile_check`` block cross-checks them against exact per-ticket
latencies from the flight recorder within the histogram's documented
bucket-error bound, and ``per_tenant_slo`` records the slowest tenants.
``--trace`` exports one Chrome trace per client count showing every
ticket's parent-linked admission->plan->dispatch->materialize spans.

Writes one JSON record (default SERVICE_r01.json) and prints it to
stdout. Diagnostics go to stderr.

Usage:
  python scripts/service_bench.py                      # 10 and 100 clients
  python scripts/service_bench.py --clients 10,100,1000 --total_queries 1000
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: dashboard-shaped parameterized templates over the NDS warehouse. Every
#: hoistable literal varies per instantiation, so instantiations of one
#: template parameterize to ONE plan fingerprint (compatible plans).
#: pool size per template: dashboard workloads repeat a SMALL set of
#: distinct texts across many users — in-window dedup (one batched row
#: serving every parameter-identical query) is the compute lever
TEMPLATES = {
    "store_qty": (
        "SELECT ss_store_sk, COUNT(*) AS n, SUM(ss_quantity) AS q "
        "FROM store_sales WHERE ss_quantity BETWEEN {a} AND {b} "
        "GROUP BY ss_store_sk ORDER BY ss_store_sk"),
    "year_sales": (
        "SELECT d_year, COUNT(*) AS n, SUM(ss_quantity) AS q "
        "FROM store_sales JOIN date_dim ON ss_sold_date_sk = d_date_sk "
        "WHERE ss_quantity < {a} GROUP BY d_year ORDER BY d_year"),
    "category_rev": (
        "SELECT i_category, COUNT(*) AS n, "
        "SUM(ss_ext_sales_price) AS rev "
        "FROM store_sales JOIN item ON ss_item_sk = i_item_sk "
        "WHERE ss_quantity BETWEEN {a} AND {b} "
        "GROUP BY i_category ORDER BY i_category"),
}
POOL_PER_TEMPLATE = 8


def build_pool() -> list[tuple[str, str]]:
    """[(label, sql)]: the shared instantiation pool clients draw from.

    Parameter ranges stay away from degenerate selectivities (an empty
    filter flips data-dependent EXACT schedule decisions, which correctly
    marks the template's shared entry volatile and disables sharing — the
    engine's contract, but not the dashboard shape this bench models)."""
    pool = []
    for name, tpl in TEMPLATES.items():
        for i in range(POOL_PER_TEMPLATE):
            pool.append((f"{name}#{i}",
                         tpl.format(a=20 + i, b=60 + 2 * i)))
    return pool


def warm_texts() -> list[tuple[str, str]]:
    """One COVERING instantiation per template: parameters chosen so its
    filter contains every pool member's (a = pool minimum, b = pool
    maximum). The capacity schedule recorded from it dominates the whole
    pool — cap checks are <=, so no pool member can ReplayMismatch a
    program warmed this way (the cap-merge loop would converge to the
    same schedule, this just skips the thrash)."""
    a_min = 20
    a_max = 20 + (POOL_PER_TEMPLATE - 1)
    b_max = 60 + 2 * (POOL_PER_TEMPLATE - 1)
    cover = {  # widest filter per template shape
        "store_qty": dict(a=a_min, b=b_max),
        "year_sales": dict(a=a_max, b=b_max),     # "< a": max a covers
        "category_rev": dict(a=a_min, b=b_max),
    }
    return [(f"warm-{name}", tpl.format(**cover[name]))
            for name, tpl in TEMPLATES.items()]


def result_hash(table) -> str:
    return hashlib.sha1(
        repr(table.to_pylist()).encode()).hexdigest()[:16]


def hist_window(before: dict, after: dict, name: str) -> dict | None:
    """The measured window's snapshot of one registry histogram series:
    after minus before (bucket counts are monotonic)."""
    from nds_tpu.obs.metrics import diff_snapshot
    if name not in after:
        return None
    return diff_snapshot(after[name], before.get(name, {}))


def _hq(snap: dict | None, p: float) -> float:
    """Histogram quantile of a window snapshot, rounded for the record."""
    from nds_tpu.obs.metrics import quantile_from_snapshot
    q = quantile_from_snapshot(snap, p) if snap else None
    return round(q, 2) if q is not None else 0.0


def _percentile_check(lat_hist: dict | None, exact_lat: list) -> dict:
    """The acceptance cross-check: registry-histogram percentiles vs the
    exact per-ticket service latencies (flight-recorder complete events),
    with the histogram's DOCUMENTED error bound (a factor of
    sqrt(BUCKET_RATIO) ≈ 1.123) recorded beside the observed ratios."""
    from nds_tpu.obs.metrics import (BUCKET_RATIO, exact_quantile,
                                     quantile_from_snapshot)
    out = {"bound_factor": round(BUCKET_RATIO ** 0.5, 4),
           "samples": len(exact_lat)}
    for p in (0.50, 0.95, 0.99):
        exact = exact_quantile(exact_lat, p)
        hist = quantile_from_snapshot(lat_hist, p) if lat_hist else None
        key = f"p{int(p * 100)}"
        out[f"exact_{key}_ms"] = round(exact, 2)
        out[f"hist_{key}_ms"] = round(hist, 2) if hist is not None else None
        if hist and exact:
            out[f"{key}_ratio"] = round(hist / exact, 4)
            out[f"{key}_within_bound"] = \
                1 / (BUCKET_RATIO ** 0.5) <= hist / exact \
                <= BUCKET_RATIO ** 0.5
    return out


def _tenant_slo(h_before: dict, h_after: dict, top: int = 8) -> list:
    """Per-tenant window SLO rows (slowest p99 first): the live-registry
    per-tenant view the acceptance criterion asks for, cut to the
    measured window via snapshot diffs."""
    from nds_tpu.obs.metrics import quantile_from_snapshot
    rows = []
    for key, snap in h_after.items():
        if snap["name"] != "service_latency_ms" or "labels" not in snap:
            continue
        win = hist_window(h_before, h_after, key)
        if not win or not win["count"]:
            continue
        rows.append({
            "tenant": snap["labels"].get("tenant"),
            "template": snap["labels"].get("template"),
            "count": win["count"],
            "p50_ms": _hq(win, 0.50), "p95_ms": _hq(win, 0.95),
            "p99_ms": _hq(win, 0.99)})
    rows.sort(key=lambda r: r["p99_ms"], reverse=True)
    return rows[:top]


def make_session(wh_dir: str):
    from nds_tpu.config import EngineConfig
    from nds_tpu.engine import Session
    from nds_tpu.power import setup_tables

    from nds_tpu.config import enable_x64
    enable_x64()
    session = Session(EngineConfig(decimal_physical="i64"))
    setup_tables(session, wh_dir, "parquet")
    return session


def workload_for(pool, clients: int, per_client: int,
                 zipf: float = 0.0):
    """Deterministic per-client query lists drawn from the shared pool.

    zipf > 0 skews the draw: pool position is popularity rank and member
    i is picked with probability ∝ (i+1)^-zipf — the template × parameter
    mix real dashboard traffic has (a few hot texts dominate), which is
    exactly the shape the semantic result cache exists for. 0 = uniform
    (the pre-r03 workload)."""
    import numpy as np
    n = len(pool)
    p = None
    if zipf > 0:
        w = np.arange(1, n + 1, dtype=float) ** (-zipf)
        p = w / w.sum()
    out = []
    for cid in range(clients):
        rng = np.random.default_rng(1000 + cid)
        picks = rng.choice(n, size=per_client, p=p) if p is not None \
            else rng.integers(0, n, per_client)
        out.append([pool[int(i)] for i in picks])
    return out


def run_serial(wh_dir: str, pool, lists, log) -> dict:
    """The baseline the service must beat: same total workload, one query
    at a time on a fresh single-caller Session."""
    from nds_tpu.engine.jax_backend.executor import clear_shared_programs
    from nds_tpu.obs.metrics import exact_quantile

    clear_shared_programs()
    session = make_session(wh_dir)
    for label, sql in warm_texts():
        session.sql(sql, label=label)
        session.sql(sql, label=label)
    hashes: dict[str, str] = {}
    lat: list[float] = []
    t0 = time.perf_counter()
    for qlist in lists:
        for label, sql in qlist:
            q0 = time.perf_counter()
            res = session.sql(sql, label=label)
            lat.append((time.perf_counter() - q0) * 1000.0)
            if sql not in hashes:
                hashes[sql] = result_hash(res)
    wall = time.perf_counter() - t0
    lat.sort()
    total = sum(len(x) for x in lists)
    rec = {"queries": total, "wall_s": round(wall, 3),
           "qps": round(total / wall, 1),
           "p50_ms": round(exact_quantile(lat, 0.50), 2),
           "p99_ms": round(exact_quantile(lat, 0.99), 2)}
    log(f"serial: {total} queries in {wall:.2f}s = {rec['qps']} QPS, "
        f"p50 {rec['p50_ms']} ms, p99 {rec['p99_ms']} ms")
    rec["_hashes"] = hashes
    return rec


def _system_poll_check(svc, h_before, h_after) -> dict:
    """The acceptance cross-check for system-table polling: per-tenant
    p50/p95/p99 computed from SQL over ``system.query_log`` (exact — the
    log holds every completion) vs the live registry histograms
    (``METRICS.percentiles``' source), within the documented ~12% bucket
    bound. The SQL fetch itself rides the system bypass — the check IS
    a system poll."""
    from nds_tpu.engine.arrow_bridge import to_arrow
    from nds_tpu.obs.metrics import (BUCKET_RATIO, exact_quantile,
                                     merge_snapshots,
                                     quantile_from_snapshot)
    bound = BUCKET_RATIO ** 0.5
    rows = to_arrow(svc.sql(
        "SELECT tenant, wall_ms FROM system.query_log "
        "WHERE status = 'ok' AND source = 'service'")).to_pylist()
    by_tenant: dict[str, list[float]] = {}
    for r in rows:
        if r["tenant"] and r["wall_ms"] is not None:
            by_tenant.setdefault(r["tenant"], []).append(r["wall_ms"])
    per = []
    n_ok = 0
    for tenant, lat in sorted(by_tenant.items()):
        merged = None
        for key, snap in h_after.items():
            if snap["name"] != "service_latency_ms" or \
                    snap.get("labels", {}).get("tenant") != tenant:
                continue
            win = hist_window(h_before, h_after, key)
            if win and win["count"]:
                merged = win if merged is None \
                    else merge_snapshots(merged, win)
        if merged is None or not merged["count"]:
            continue
        lat.sort()
        row = {"tenant": tenant, "n": len(lat),
               "hist_n": merged["count"]}
        ok = True
        for p in (0.50, 0.95, 0.99):
            e = exact_quantile(lat, p)
            h = quantile_from_snapshot(merged, p)
            key_p = f"p{int(p * 100)}"
            row[f"sql_{key_p}"] = round(e, 2)
            row[f"hist_{key_p}"] = round(h, 2) if h is not None else None
            if h and e:
                r = h / e
                row[f"{key_p}_ratio"] = round(r, 4)
                ok = ok and (1 / bound - 1e-9 <= r <= bound + 1e-9)
        row["within_bound"] = ok and len(lat) == merged["count"]
        n_ok += row["within_bound"]
        per.append(row)
    return {"bound_factor": round(bound, 4),
            "tenants": len(per),
            "tenants_within_bound": n_ok,
            "all_within_bound": n_ok == len(per) and len(per) > 0,
            "rows": per}


def run_service(wh_dir: str, pool, clients: int, lists,
                serial_hashes: dict, record_queries: int, log,
                trace_dir: str | None = None,
                flight_dump: str | None = None,
                cache: bool = False,
                pollers: int = 0,
                query_log: str | None = None) -> dict:
    from nds_tpu.engine.jax_backend.executor import clear_shared_programs
    from nds_tpu.obs.flight import FLIGHT
    from nds_tpu.obs.metrics import METRICS
    from nds_tpu.obs.query_log import QUERY_LOG
    from nds_tpu.obs.trace import TRACER
    from nds_tpu.service import (QueryService, ResultCacheConfig,
                                 ServiceConfig)

    clear_shared_programs()
    session = make_session(wh_dir)
    cfg = ServiceConfig(max_pending=256, max_batch=64,
                        batch_linger_ms=5.0,
                        result_cache=ResultCacheConfig(subsumption=True)
                        if cache else None)
    svc = QueryService(session, cfg).start()
    try:
        for label, sql in warm_texts():
            svc.sql(sql, label=label)
            svc.sql(sql, label=label)
        if cache:
            # steady-state dashboard model: one pass over the pool
            # populates the result cache (each text executes once), so
            # the measured window is pure REPEAT traffic — the shape the
            # acceptance pins with counts: zero planner samples, zero
            # device dispatches, every completion a cache hit
            for label, sql in pool:
                svc.sql(sql, label=f"prewarm-{label}")
        # batch-shape warmup: the measured window's batched dispatches pad
        # to capacity-ladder buckets of their UNIQUE row counts — compile
        # every bucket up to max_batch now (held bursts of b distinct
        # instantiations -> cap bucket(b); a duplicate pair -> cap 1) so
        # compiles stay flat while the clock runs. With the result cache
        # armed this is SKIPPED: repeats answer at admission (they never
        # park at the lane, so held tickets would stall the hold loop) and
        # only the ~pool-size cold texts ever dispatch
        sizes = [] if cache else [1]
        b = 2
        while not cache and b <= min(cfg.max_batch,
                                     POOL_PER_TEMPLATE - 1):
            sizes.append(b)
            b = 2 * b - 1          # 2,3,5,9,17,33: caps 2,4,8,16,32,64
        for ti in range(len(TEMPLATES) if sizes else 0):
            base = ti * POOL_PER_TEMPLATE
            for bsize in sizes:
                with svc.hold_dispatch():
                    if bsize == 1:   # duplicate pair dedups to one row
                        picks = [pool[base], pool[base]]
                    else:
                        picks = [pool[base + j] for j in range(bsize)]
                    tickets = [svc.submit(sql, label=f"shape-{label}")
                               for label, sql in picks]
                    deadline = time.time() + 60
                    while time.time() < deadline:
                        with svc._cv:
                            if len(svc._ready) >= len(tickets):
                                break
                        time.sleep(0.005)
                for t in tickets:
                    t.result(timeout=600)

        per_query: list[dict] = []
        mismatches: list[str] = []
        errors: list[str] = []
        rejection_retries = [0]
        lock = threading.Lock()

        def client(cid, qlist):
            """OPEN-LOOP client: submits its whole list up front (arrival
            independent of completion — queue depth is the service's
            problem, shed via typed AdmissionRejected which the client
            retries with backoff, the intended overload protocol), then
            collects every result."""
            from nds_tpu.resilience import AdmissionRejected
            rows = []
            submitted = []
            for label, sql in qlist:
                q0 = time.perf_counter()
                backoff = 0.05
                while True:
                    try:
                        t = svc.submit(sql, label=label, tenant=f"c{cid}")
                        break
                    except AdmissionRejected:
                        with lock:
                            rejection_retries[0] += 1
                        time.sleep(backoff)
                        backoff = min(1.0, backoff * 2)
                submitted.append((label, sql, q0, t))
            for label, sql, q0, ticket in submitted:
                try:
                    res = ticket.result(timeout=600)
                except Exception as e:
                    with lock:
                        errors.append(f"{label}: {type(e).__name__}: {e}")
                    continue
                ms = (time.perf_counter() - q0) * 1000.0
                st = ticket.stats
                rows.append({
                    "label": label, "client": cid,
                    "latency_ms": round(ms, 2),
                    "queue_wait_ms": st.queue_wait_ms if st else None,
                    "batched_with": st.batched_with if st else None,
                    "mode": st.mode if st else None,
                })
                if result_hash(res) != serial_hashes.get(sql):
                    with lock:
                        mismatches.append(label)
            with lock:
                per_query.extend(rows)

        # the measured window's observability state: the flight recorder
        # rides along (sized to hold the whole window) and the histogram
        # cut isolates the window from warmup via snapshot diffs
        # ~4 ring events per query (admit/plan/complete + shared batch
        # rows) — size so the window's completes all survive eviction
        FLIGHT.configure(enabled=True,
                         capacity=4 * sum(len(x) for x in lists) + 512,
                         clear=True)
        if trace_dir:
            TRACER.configure(enabled=True)
        if pollers or query_log:
            # the durable query log covers exactly the measured window:
            # ring sized to hold every completion (the SQL-vs-histogram
            # cross-check needs the full sample set), JSONL opt-in
            QUERY_LOG.configure(
                enabled=True,
                capacity=sum(len(x) for x in lists) + 256,
                path=query_log, clear=True)
        poll_stats = {"polls": 0, "errors": 0, "last_rows": 0}
        poll_stop = threading.Event()

        def poller(pid):
            """Concurrent operator: SQL over system.query_log +
            system.histograms WHILE the workload runs — through the
            service's admission bypass (svc.submit), as a live operator
            would."""
            polls = [
                ("SELECT tenant, COUNT(*) AS n FROM system.query_log "
                 "GROUP BY tenant"),
                ("SELECT series, total_count FROM system.histograms "
                 "WHERE name = 'service_latency_ms'"),
                ("SELECT name, value FROM system.metrics "
                 "WHERE name = 'service_queue_depth'"),
            ]
            i = pid
            while not poll_stop.is_set():
                try:
                    res = svc.sql(polls[i % len(polls)],
                                  label=f"poll{pid}")
                    with lock:
                        poll_stats["polls"] += 1
                        poll_stats["last_rows"] = res.num_rows
                except Exception:
                    with lock:
                        poll_stats["errors"] += 1
                i += 1
                # operator cadence, not a tight loop: this 1-core host
                # shares the poll's host-side CPU with the workload, so
                # the poll RATE is the wall-clock knob (the zero-device-
                # work/zero-compile pins hold at any rate)
                time.sleep(0.5)

        before = METRICS.snapshot()
        h_before = METRICS.histograms()
        threads = [threading.Thread(target=client, args=(cid, ql))
                   for cid, ql in enumerate(lists)]
        poll_threads = [threading.Thread(target=poller, args=(i,))
                        for i in range(pollers)]
        t0 = time.perf_counter()
        for t in threads + poll_threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        poll_stop.set()
        for t in poll_threads:
            t.join()
        delta = METRICS.delta(before)
        h_after = METRICS.histograms()
        system_poll = None
        if pollers:
            system_poll = _system_poll_check(svc, h_before, h_after)
            system_poll["polls"] = poll_stats["polls"]
            system_poll["poll_errors"] = poll_stats["errors"]
        if query_log:
            QUERY_LOG.flush()
    finally:
        svc.close()

    trace_file = None
    if trace_dir:
        trace_file = TRACER.write_chrome_trace(os.path.join(
            trace_dir, f"service_trace_c{clients}.json"))
        TRACER.configure(enabled=False)
        log(f"trace: {trace_file} (open in ui.perfetto.dev)")
    flight_file = None
    if flight_dump:
        flight_file = FLIGHT.dump_jsonl(
            flight_dump.replace(".jsonl", f"_c{clients}.jsonl"))
    # service-side latency percentiles now come from the REGISTRY
    # histograms (the per-tenant/per-template SLO source every consumer
    # shares) — cross-checked below against exact per-ticket latencies
    # from the flight recorder's complete events, within the documented
    # bucket error bound
    lat_hist = hist_window(h_before, h_after, "service_latency_ms")
    wait_hist = hist_window(h_before, h_after, "service_queue_wait_ms")
    exact_lat = sorted(e["latency_ms"] for e in FLIGHT.events()
                       if e["event"] == "complete")
    FLIGHT.configure(enabled=False)
    batched = [r for r in per_query if (r["batched_with"] or 0) > 0]
    total = sum(len(x) for x in lists)
    rec = {
        "clients": clients,
        "result_cache": cache,
        "queries": total,
        "completed": len(per_query),
        "errors": errors[:10],
        "wall_s": round(wall, 3),
        "qps": round(len(per_query) / wall, 1) if wall else 0.0,
        "p50_ms": _hq(lat_hist, 0.50),
        "p99_ms": _hq(lat_hist, 0.99),
        "queue_wait_p50_ms": _hq(wait_hist, 0.50),
        "queue_wait_p99_ms": _hq(wait_hist, 0.99),
        "percentile_check": _percentile_check(lat_hist, exact_lat),
        "per_tenant_slo": _tenant_slo(h_before, h_after, top=8),
        # the raw window snapshots: any quantile is recomputable offline
        # (obs_report / quantile_from_snapshot), and shard-level records
        # merge via merge_snapshots
        "latency_hist": lat_hist,
        "queue_wait_hist": wait_hist,
        "batched_frac": round(len(batched) / max(1, len(per_query)), 3),
        "admission_rejection_retries": rejection_retries[0],
        # engine-counter delta over the MEASURED window (warmup excluded):
        # compiles ~0 proves the shared cache keeps programs flat; batches
        # and adoption quantify how the queries were actually served
        "metrics_delta": {k: delta[k] for k in sorted(delta)
                          if k.split("_")[0] in
                          ("service", "compiles", "program", "programs",
                           "queries", "replay", "result", "system",
                           "query")},
        "results_identical_to_serial": not mismatches,
        "result_mismatches": mismatches[:10],
        # the per-query block (capped): latency decomposed into wait vs
        # execute, plus who rode a shared batched dispatch
        "queries_sample": per_query[:record_queries],
    }
    if cache:
        # the acceptance pins, COUNTS ONLY (single-core host wall times
        # flake; they stay report-only): repeat-template tickets complete
        # with zero planner/device work, and every response hashed
        # identical to the uncached serial baseline
        texts = {sql for ql in lists for _l, sql in ql}
        executed = int(delta.get("queries_run", 0))
        hits = int(delta.get("result_cache_hits", 0)
                   + delta.get("result_cache_subsumption_hits", 0))
        plan_win = hist_window(h_before, h_after, "service_plan_ms")
        plan_n = int(plan_win["count"]) if plan_win else 0
        rec["cache_assertions"] = {
            "distinct_texts": len(texts),
            "executed_queries": executed,
            "cache_hits": hits,
            "plan_stage_samples": plan_n,
            # the pool was pre-warmed, so the window is all repeats:
            # ZERO planner samples and ZERO device dispatches, pinned by
            # counts (service_plan_ms count / queries_run / batches)
            "repeat_tickets_zero_planner_work": plan_n == 0,
            "repeat_tickets_zero_device_work":
                executed == 0 and not delta.get("service_batches")
                and not delta.get("compiles"),
            # every completion was a cache hit
            "hits_cover_all_repeats": hits == len(per_query),
            "hash_identical_to_uncached_baseline": not mismatches,
        }
    if system_poll is not None:
        # the acceptance block: per-tenant SQL-exact vs registry-
        # histogram percentiles within the documented bound, plus how
        # many concurrent polls rode the window
        rec["system_poll"] = system_poll
    if query_log:
        rec["query_log"] = query_log
    if trace_file:
        rec["trace_file"] = trace_file
    if flight_file:
        rec["flight_file"] = flight_file
    log(f"clients={clients}{' cache' if cache else ''}: "
        f"{rec['qps']} QPS ({total} queries in "
        f"{wall:.2f}s), p50 {rec['p50_ms']} ms, p99 {rec['p99_ms']} ms, "
        f"batched {rec['batched_frac']:.0%}, "
        f"compiles {delta.get('compiles', 0)}, "
        f"cache_hits {delta.get('result_cache_hits', 0)}, "
        f"identical={rec['results_identical_to_serial']}")
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="service_bench.py", description=(
        "open-loop query-service bench: sustained QPS + p50/p99 latency "
        "at N concurrent clients vs the serial baseline"))
    p.add_argument("--clients", default="10,100",
                   help="comma list of concurrent-client counts")
    p.add_argument("--total_queries", type=int, default=1000,
                   help="total workload per measured run (split evenly "
                        "across clients, so every client count measures "
                        "the same amount of work)")
    p.add_argument("--record_queries", type=int, default=200,
                   help="per-query rows kept in the JSON (cap)")
    p.add_argument("--zipf", type=float, default=0.0,
                   help="Zipf skew over the template x parameter pool "
                        "(pool position = popularity rank, pick prob "
                        "~ rank^-S); 0 = uniform")
    p.add_argument("--cache", choices=["off", "on", "both"],
                   default="off",
                   help="arm the semantic result cache for the measured "
                        "runs; 'both' measures each client count "
                        "uncached THEN cached (the SERVICE_r03 shape: "
                        "counts-based zero-work assertions + hash "
                        "identity vs the uncached baseline)")
    p.add_argument("--trace", action="store_true",
                   help="span-trace each measured window; writes one "
                        "Chrome trace-event file per client count "
                        "(service_trace_cN.json beside --out) showing the "
                        "parent-linked admission->plan->dispatch->"
                        "materialize spans of every ticket")
    p.add_argument("--flight", action="store_true",
                   help="also dump each measured window's flight-recorder "
                        "ring as service_flight_cN.jsonl beside --out "
                        "(the ring records regardless — it feeds the "
                        "exact-percentile cross-check)")
    p.add_argument("--poll_system", type=int, default=0, metavar="N",
                   help="run N concurrent system-table poller threads "
                        "(SQL over system.query_log / system.histograms "
                        "/ system.metrics through the service's "
                        "admission bypass) DURING each measured window; "
                        "each client count then runs PAIRED — unpolled "
                        "baseline, then polled — and the record carries "
                        "the per-tenant SQL-vs-histogram percentile "
                        "cross-check plus a zero-added-work comparison "
                        "(compiles/dispatch counters equal, responses "
                        "hash-identical in both runs)")
    p.add_argument("--query_log", default=None, metavar="PATH",
                   help="enable the durable query log for the measured "
                        "windows and write the JSONL here (per client "
                        "count: PATH gains a _cN suffix) — "
                        "scripts/slo_report.py reproduces the SLO "
                        "numbers offline from it")
    p.add_argument("--out", default=os.path.join(REPO, "SERVICE_r01.json"))
    p.add_argument("--sf", default="0.01")
    a = p.parse_args(argv)

    from benchmark.run import ensure_warehouse
    from nds_tpu.config import maybe_enable_compile_cache
    maybe_enable_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    wh_dir = ensure_warehouse(a.sf)
    pool = build_pool()
    counts = [int(x) for x in a.clients.split(",") if x.strip()]

    def lists_for(clients):
        per_client = max(1, -(-a.total_queries // clients))
        return workload_for(pool, clients, per_client, zipf=a.zipf)

    # the serial baseline runs the same total workload one query at a
    # time; every client count re-runs ~the same total, so QPS compares
    # equal sustained work, not unequal totals
    serial = run_serial(wh_dir, pool, lists_for(max(counts)), log)
    hashes = serial.pop("_hashes")
    out_dir = os.path.dirname(os.path.abspath(a.out))
    runs = []
    cache_modes = {"off": [False], "on": [True],
                   "both": [False, True]}[a.cache]
    #: counters whose window delta must be EQUAL between the unpolled
    #: baseline and the polled run — system polls must add zero compile/
    #: device/replay work (system_queries itself is the only expected
    #: mover). Batch COMPOSITION counters (service_batches,
    #: program_cache_misses) are reported beside but not pinned: under
    #: open-loop admission the drain windows are thread-timing-dependent
    #: run to run (batch_linger_ms=0 serves whatever is queued), polls
    #: or no polls
    PIN = ("compiles", "queries_run", "replay_mismatches")
    INFO = ("service_batches", "service_batched_queries",
            "program_cache_misses")
    for c in counts:
        for cached in cache_modes:
            passes = [0, a.poll_system] if a.poll_system else [0]
            pair = []
            for pollers in passes:
                ql = None
                if a.query_log and (pollers or not a.poll_system):
                    ql = a.query_log.replace(".jsonl", f"_c{c}.jsonl")
                rec = run_service(
                    wh_dir, pool, c, lists_for(c), hashes,
                    a.record_queries, log,
                    trace_dir=out_dir if a.trace else None,
                    flight_dump=os.path.join(out_dir,
                                             "service_flight.jsonl")
                    if a.flight else None,
                    cache=cached, pollers=pollers, query_log=ql)
                rec["speedup_vs_serial_qps"] = round(
                    rec["qps"] / serial["qps"], 2) if serial["qps"] \
                    else None
                rec["polled"] = bool(pollers)
                pair.append(rec)
                runs.append(rec)
            if len(pair) == 2:
                base, polled = pair
                bd, pd = base["metrics_delta"], polled["metrics_delta"]
                polled["system_poll_overhead"] = {
                    # the acceptance pins, COUNTS ONLY: the polled window
                    # compiled nothing extra, dispatched the same query
                    # count, replayed nothing wrong — polls added
                    # system_queries and NOTHING on those axes
                    "pinned_counters": {k: {"baseline": bd.get(k, 0),
                                            "polled": pd.get(k, 0)}
                                        for k in PIN},
                    "pins_equal": all(bd.get(k, 0) == pd.get(k, 0)
                                      for k in PIN),
                    "batching_composition": {
                        k: {"baseline": bd.get(k, 0),
                            "polled": pd.get(k, 0)} for k in INFO},
                    "system_queries_polled": pd.get("system_queries", 0),
                    "both_hash_identical_to_serial":
                        base["results_identical_to_serial"]
                        and polled["results_identical_to_serial"],
                }
                log(f"clients={c} polled-vs-unpolled pins equal: "
                    f"{polled['system_poll_overhead']['pins_equal']} "
                    f"(system_queries="
                    f"{pd.get('system_queries', 0)})")

    import platform

    from nds_tpu.report import device_capture
    out = {
        "schema_version": 3,
        "kind": "service_open_loop",
        "sf": a.sf,
        "templates": {k: v for k, v in TEMPLATES.items()},
        "pool_per_template": POOL_PER_TEMPLATE,
        "total_queries": a.total_queries,
        "zipf": a.zipf,
        "cache_mode": a.cache,
        "platform": {"python": platform.python_version(),
                     "machine": platform.machine()},
        # the device the engine ran on, as JAX reports it: on "cpu" the
        # 'device' executes on the load generator's own cores, so QPS
        # gains there come from batching + pipelining + shared programs,
        # not from host/accelerator overlap
        "device": device_capture(),
        "serial": serial,
        "runs": runs,
    }
    with open(a.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    log(f"record: {a.out}")
    print(json.dumps({k: v for k, v in out.items()
                      if k not in ("templates", "runs")} |
                     {"runs": [{k: v for k, v in r.items()
                                if k != "queries_sample"}
                               for r in runs]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
