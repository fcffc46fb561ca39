#!/usr/bin/env python
"""Distributed-serving bench -> FRONTDOOR_r01.json (the PR acceptance
artifact): interactive p99 under weighted-fair + morsel-boundary
preemption vs the FIFO baseline, measured across REAL OS process
boundaries.

The shape (all against the chaos demo dataset — fact/dim in-core at
``out_of_core_min_rows=30_000``, sfact parquet streamed):

1. **Serial baseline** — a fresh in-process Session hashes every
   distinct workload statement (the canonical engine-table hash the
   server ships per response); every wire response in every phase must
   match bit-for-bit.
2. **In-process reference** — the same mixed workload through
   ``QueryService.submit`` directly (threads, no wire): the QPS
   ceiling the front door is compared against.
3. **FIFO phase** — one engine process behind the Arrow-IPC front
   door, scheduler flags off. Two WORKER PROCESSES (spawned copies of
   this script with ``--worker``) run 50 client threads each: the
   ``interactive`` tenant paces short in-core lookups while the
   ``batch`` tenant saturates the device lane with streamed scans —
   the convoy: every interactive arrival queues behind every
   already-queued scan.
4. **Fair phase** — identical workload, identical engine config, the
   server restarted with ``--fair_queue --tenant_weights
   interactive=4,batch=1 --preemption``: per-tenant weighted deficit
   queues + streamed queries yielding the lane between scan groups.
5. Both phases read per-tenant latency from ``system.query_log`` OVER
   THE WIRE (the server runs ``--query_log``) — the engine reports its
   own p99, the bench never trusts client clocks for the headline.
6. **Chaos round** — ``nds_tpu.chaos.run_topology_campaign``:
   connection drops, one engine-process kill mid-query (exit 86), a
   replacement server, and the stale-cache invariant (a snapshot
   warmed from the dead epoch must validate False, re-fetch, and still
   hash-identical).

Workers synchronize on a stdin GO line after connecting all sockets,
so measured wall excludes interpreter/import/connect cost; each server
is warmed (every distinct statement, tenant ``warmup``) before the
measured window, so the phases compare scheduling, not compilation.

Usage:
  python scripts/frontdoor_bench.py                  # full acceptance run
  python scripts/frontdoor_bench.py --quick          # small smoke shape
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: identical engine shape for baseline/in-process/servers: fact (20k
#: rows) stays in-core (batched dispatch), sfact (60k rows) streams in
#: 4096-row morsels — the preemption yield points
ENGINE_KW = dict(chunk_rows=4096, out_of_core_min_rows=30_000)
TENANT_WEIGHTS = "interactive=4,batch=1"


def build_workload(seed: int, n_interactive: int, n_batch: int,
                   q_interactive: int, q_batch: int) -> dict:
    """Seeded per-thread query lists for both tenants (the same lists
    replay against FIFO, fair, and the in-process reference)."""
    import random

    from nds_tpu.chaos import demo_pool

    pool = demo_pool()
    incore = [p for p in pool if p[0].startswith("incore")]
    streamed = [p for p in pool if p[0].startswith("streamed")]
    rng = random.Random(seed)
    return {
        "interactive": {
            str(i): [list(incore[rng.randrange(len(incore))])
                     for _ in range(q_interactive)]
            for i in range(n_interactive)},
        "batch": {
            str(i): [list(streamed[rng.randrange(len(streamed))])
                     for _ in range(q_batch)]
            for i in range(n_batch)},
    }


def distinct_sqls(workload: dict) -> list:
    out = []
    for threads in workload.values():
        for queries in threads.values():
            for _label, sql in queries:
                if sql not in out:
                    out.append(sql)
    return out


# -- worker process mode ----------------------------------------------------

def run_worker(cfg_path: str) -> int:
    """One OS client process: N threads, one FlightClient socket each,
    replaying this worker's query lists against the server and checking
    every response hash against the serial baseline. Prints WORKERREADY
    once every socket is connected, blocks on a stdin GO line, then
    prints one WORKERRESULT json line."""
    from nds_tpu.obs.metrics import exact_quantile
    from nds_tpu.service.frontdoor import FlightClient

    with open(cfg_path) as f:
        cfg = json.load(f)
    tenant = cfg["tenant"]
    baseline = cfg["baseline"]
    pace_s = float(cfg.get("pace_s") or 0.0)
    clients = {tid: FlightClient("127.0.0.1", cfg["port"], retries=3)
               for tid in cfg["threads"]}
    for c in clients.values():
        c.ping()
    print("WORKERREADY", flush=True)
    sys.stdin.readline()          # the GO barrier

    lock = threading.Lock()
    state = {"completed": 0, "checked": 0, "mismatches": 0,
             "failed": {}, "untyped": [], "lat_ms": []}

    def client(tid: str, queries: list) -> None:
        c = clients[tid]
        for label, sql in queries:
            t0 = time.perf_counter()
            try:
                _table, hdr = c.query(sql, tenant=tenant, label=label,
                                      want_hash=True)
            except Exception as e:
                from nds_tpu.chaos import is_typed
                with lock:
                    if is_typed(e):
                        name = type(e).__name__
                        state["failed"][name] = \
                            state["failed"].get(name, 0) + 1
                    else:
                        state["untyped"].append(
                            f"{label}: {type(e).__name__}: {e}")
                continue
            ms = (time.perf_counter() - t0) * 1000.0
            with lock:
                state["completed"] += 1
                state["lat_ms"].append(ms)
                if sql in baseline:
                    state["checked"] += 1
                    if hdr.get("result_hash") != baseline[sql]:
                        state["mismatches"] += 1
            if pace_s:
                time.sleep(pace_s)
        c.close()

    threads = [threading.Thread(target=client, args=(tid, qs),
                                name=f"bench-{tenant}-{tid}", daemon=True)
               for tid, qs in cfg["threads"].items()]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lat = sorted(state["lat_ms"])
    print("WORKERRESULT " + json.dumps({
        "tenant": tenant, "threads": len(threads),
        "wall_s": round(time.perf_counter() - t0, 3),
        "completed": state["completed"], "checked": state["checked"],
        "mismatches": state["mismatches"], "failed": state["failed"],
        "untyped": state["untyped"][:10],
        "untyped_count": len(state["untyped"]),
        "client_p50_ms": round(exact_quantile(lat, 0.50), 2) if lat else 0,
        "client_p99_ms": round(exact_quantile(lat, 0.99), 2) if lat else 0,
    }), flush=True)
    return 0


# -- parent orchestration ---------------------------------------------------

def _warm(port: int, sqls: list) -> None:
    """Compile every distinct statement before the measured window
    (tenant 'warmup' rows are excluded from the per-tenant log stats)."""
    from nds_tpu.service.frontdoor import FlightClient

    c = FlightClient("127.0.0.1", port)
    for sql in sqls:
        for _ in range(2):
            c.sql(sql, tenant="warmup", label="warmup")
    c.close()


def _log_stats(port: int) -> dict:
    """Per-tenant latency FROM THE ENGINE: SQL over system.query_log
    through the same wire the workload used."""
    from nds_tpu.obs.metrics import exact_quantile
    from nds_tpu.service.frontdoor import FlightClient

    c = FlightClient("127.0.0.1", port)
    rows = c.sql("SELECT tenant, status, wall_ms, queue_ms, exec_ms, "
                 "preempted FROM system.query_log",
                 tenant="bench", label="log_read").to_pylist()
    c.close()
    out = {}
    for tenant in ("interactive", "batch"):
        mine = [r for r in rows if r["tenant"] == tenant]
        lat = sorted(r["wall_ms"] for r in mine
                     if r["wall_ms"] is not None)
        qs = [r["queue_ms"] or 0.0 for r in mine]
        if not mine:
            continue
        out[tenant] = {
            "count": len(mine),
            "errors": sum(1 for r in mine if r["status"] != "ok"),
            "p50_ms": round(exact_quantile(lat, 0.50), 2) if lat else 0,
            "p95_ms": round(exact_quantile(lat, 0.95), 2) if lat else 0,
            "p99_ms": round(exact_quantile(lat, 0.99), 2) if lat else 0,
            "mean_queue_ms": round(sum(qs) / len(qs), 2) if qs else 0,
            "preempted": sum(int(r["preempted"] or 0) for r in mine),
        }
    return out


def run_wire_phase(name: str, server_flags: list, workload: dict,
                   baseline: dict, pace: dict, tmp: str) -> dict:
    """Spawn one engine server + one worker PROCESS per tenant, release
    them together, and report engine-side + client-side stats."""
    from nds_tpu.chaos import _spawn_frontdoor

    base = ["--demo", "--query_log",
            "--chunk_rows", str(ENGINE_KW["chunk_rows"]),
            "--out_of_core_min_rows",
            str(ENGINE_KW["out_of_core_min_rows"])]
    proc, info = _spawn_frontdoor(base + server_flags)
    port = info["port"]
    workers = []
    try:
        _warm(port, distinct_sqls(workload))
        for tenant, threads in workload.items():
            cfg_path = os.path.join(tmp, f"{name}_{tenant}.json")
            with open(cfg_path, "w") as f:
                json.dump({"port": port, "tenant": tenant,
                           "threads": threads, "baseline": baseline,
                           "pace_s": pace.get(tenant, 0.0)}, f)
            w = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--worker", cfg_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            workers.append((tenant, w))
        for _tenant, w in workers:          # all sockets connected?
            line = w.stdout.readline()
            if not line.startswith("WORKERREADY"):
                raise RuntimeError(f"worker failed to start: {line!r}")
        t0 = time.perf_counter()
        for _tenant, w in workers:          # the GO barrier
            w.stdin.write("GO\n")
            w.stdin.flush()
        results = {}
        for tenant, w in workers:
            line = w.stdout.readline()
            while line and not line.startswith("WORKERRESULT "):
                line = w.stdout.readline()
            if not line:
                raise RuntimeError(f"worker {tenant} died without result")
            results[tenant] = json.loads(line.split(" ", 1)[1])
        wall = time.perf_counter() - t0
        engine = _log_stats(port)
    finally:
        for _tenant, w in workers:
            try:
                w.stdin.close()
                w.wait(timeout=30)
            except Exception:
                w.kill()
        try:
            proc.stdin.close()
            proc.wait(timeout=30)
        except Exception:
            proc.kill()
    completed = sum(r["completed"] for r in results.values())
    return {"phase": name, "server": info, "wall_s": round(wall, 3),
            "completed": completed,
            "qps": round(completed / wall, 2) if wall else 0.0,
            "engine_log": engine, "workers": results}


def run_inproc_reference(workload: dict, pace: dict, tmp: str) -> dict:
    """The same mixed workload through QueryService.submit in ONE
    process (fair + preemption armed): the no-wire QPS reference."""
    from nds_tpu.chaos import build_demo_session
    from nds_tpu.service import QueryService, ServiceConfig

    session = build_demo_session(os.path.join(tmp, "inproc"), **ENGINE_KW)
    weights = dict(p.split("=") for p in TENANT_WEIGHTS.split(","))
    svc = QueryService(session, ServiceConfig(
        fair_queue=True,
        tenant_weights={k: float(v) for k, v in weights.items()},
        preemption=True, preempt_max=4))
    svc.start()
    try:
        for sql in distinct_sqls(workload):
            for _ in range(2):
                svc.submit(sql, tenant="warmup").result(timeout=300)
        lock = threading.Lock()
        state = {"completed": 0, "failed": 0}

        def client(tenant: str, queries: list) -> None:
            pace_s = pace.get(tenant, 0.0)
            for label, sql in queries:
                try:
                    svc.submit(sql, tenant=tenant,
                               label=label).result(timeout=300)
                except Exception:
                    with lock:
                        state["failed"] += 1
                    continue
                with lock:
                    state["completed"] += 1
                if pace_s:
                    time.sleep(pace_s)

        threads = [threading.Thread(target=client, args=(tenant, qs),
                                    daemon=True)
                   for tenant, per_thread in workload.items()
                   for qs in per_thread.values()]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
    finally:
        svc.close()
    return {"clients": len(threads), "wall_s": round(wall, 3),
            "completed": state["completed"], "failed": state["failed"],
            "qps": round(state["completed"] / wall, 2) if wall else 0.0}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="frontdoor_bench.py", description=(
        "mixed-traffic front-door bench: FIFO vs weighted-fair + "
        "preemption across OS process boundaries -> FRONTDOOR_r01.json"))
    p.add_argument("--worker", default=None, metavar="CFG_JSON",
                   help=argparse.SUPPRESS)   # internal: client process
    p.add_argument("--seed", type=int, default=0xC0FFEE)
    p.add_argument("--interactive_clients", type=int, default=50)
    p.add_argument("--batch_clients", type=int, default=50)
    p.add_argument("--interactive_queries", type=int, default=6,
                   help="paced in-core lookups per interactive thread")
    p.add_argument("--batch_queries", type=int, default=4,
                   help="back-to-back streamed scans per batch thread")
    p.add_argument("--pace_s", type=float, default=0.05,
                   help="interactive think time between queries")
    p.add_argument("--quick", action="store_true",
                   help="small smoke shape (8+8 clients, no chaos)")
    p.add_argument("--skip_chaos", action="store_true")
    p.add_argument("--out", default=os.path.join(REPO,
                                                 "FRONTDOOR_r01.json"))
    a = p.parse_args(argv)
    if a.worker:
        return run_worker(a.worker)

    # this parent runs an in-process baseline AND spawns engine server
    # processes: on an accelerator it would hold the chip its servers need.
    # It is a host-side scheduling comparison (FRONTDOOR_r01.json says so);
    # ask for the CPU by name or be refused up front (ROADMAP R4 is the
    # chip cell: one server process, JAX-free clients).
    from nds_tpu.resilience import check_child_placement
    check_child_placement("frontdoor_bench.py")
    if a.quick:
        a.interactive_clients = a.batch_clients = 8
        a.interactive_queries, a.batch_queries = 3, 2
        a.skip_chaos = True

    from nds_tpu.chaos import (TOPOLOGY_POINTS, CampaignSpec,
                               build_demo_session, result_hash,
                               run_topology_campaign)

    tmp = tempfile.mkdtemp(prefix="frontdoor_bench_")
    workload = build_workload(a.seed, a.interactive_clients,
                              a.batch_clients, a.interactive_queries,
                              a.batch_queries)
    pace = {"interactive": a.pace_s, "batch": 0.0}

    # 1. serial baseline hashes (fresh session, same engine shape)
    t0 = time.perf_counter()
    base_session = build_demo_session(os.path.join(tmp, "baseline"),
                                      **ENGINE_KW)
    baseline = {sql: result_hash(base_session.sql(sql))
                for sql in distinct_sqls(workload)}
    baseline_s = round(time.perf_counter() - t0, 3)
    print(f"frontdoor_bench: serial baseline hashed "
          f"{len(baseline)} statements in {baseline_s}s", file=sys.stderr)

    # 2. in-process QPS reference
    inproc = run_inproc_reference(workload, pace, tmp)
    print(f"frontdoor_bench: in-process reference "
          f"{inproc['qps']} qps", file=sys.stderr)

    # 3/4. the wire phases: FIFO baseline, then fair + preemption
    fifo = run_wire_phase("fifo", [], workload, baseline, pace, tmp)
    print(f"frontdoor_bench: fifo phase {fifo['qps']} qps, interactive "
          f"p99 {fifo['engine_log']['interactive']['p99_ms']} ms",
          file=sys.stderr)
    fair = run_wire_phase(
        "fair", ["--fair_queue", "--tenant_weights", TENANT_WEIGHTS,
                 "--preemption", "--preempt_max", "4"],
        workload, baseline, pace, tmp)
    print(f"frontdoor_bench: fair phase {fair['qps']} qps, interactive "
          f"p99 {fair['engine_log']['interactive']['p99_ms']} ms",
          file=sys.stderr)

    # 6. chaos over the topology: drop + engine kill + recovery
    chaos = None
    if not a.skip_chaos:
        spec = CampaignSpec(seed=a.seed, clients=8, queries_per_client=6,
                            points=TOPOLOGY_POINTS, probability=0.35,
                            times_per_point=2)
        chaos = run_topology_campaign(spec, os.path.join(tmp, "chaos"))
        print(f"frontdoor_bench: chaos invariants "
              f"{chaos['invariants']}", file=sys.stderr)

    p99_fifo = fifo["engine_log"]["interactive"]["p99_ms"]
    p99_fair = fair["engine_log"]["interactive"]["p99_ms"]
    mism = sum(r["mismatches"] for ph in (fifo, fair)
               for r in ph["workers"].values())
    checked = sum(r["checked"] for ph in (fifo, fair)
                  for r in ph["workers"].values())
    from nds_tpu.report import device_capture
    record = {
        "schema_version": 2,
        "device": device_capture(),
        "config": {
            "seed": a.seed, "engine": dict(ENGINE_KW),
            "tenant_weights": TENANT_WEIGHTS,
            "interactive_clients": a.interactive_clients,
            "batch_clients": a.batch_clients,
            "clients_total": a.interactive_clients + a.batch_clients,
            "client_processes": 2,
            "interactive_queries": a.interactive_queries,
            "batch_queries": a.batch_queries, "pace_s": a.pace_s},
        "serial_baseline": {"statements": len(baseline),
                            "wall_s": baseline_s},
        "inproc": inproc,
        "phases": {"fifo": fifo, "fair": fair},
        "comparison": {
            "interactive_p99_fifo_ms": p99_fifo,
            "interactive_p99_fair_ms": p99_fair,
            "interactive_p99_speedup": round(p99_fifo / p99_fair, 2)
            if p99_fair else None,
            "preemptions":
                fair["engine_log"]["batch"]["preempted"],
            "wire_qps_vs_inproc": round(fair["qps"] / inproc["qps"], 3)
            if inproc["qps"] else None},
        "hash_identity": {"checked": checked, "mismatches": mism},
        "chaos": chaos,
        "invariants": {
            "interactive_p99_improved": p99_fair < p99_fifo,
            "all_hashes_identical": mism == 0 and checked > 0,
            "preemption_observed":
                fair["engine_log"]["batch"]["preempted"] > 0,
            "multiprocess": True,
            **({f"chaos_{k}": v
                for k, v in chaos["invariants"].items()} if chaos
               else {}),
        },
    }
    with open(a.out, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps({"out": a.out, "comparison": record["comparison"],
                      "invariants": record["invariants"]},
                     indent=2, sort_keys=True))
    ok = all(record["invariants"].values())
    print(f"frontdoor_bench: {'OK' if ok else 'INVARIANT FAILURES'}",
          file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
