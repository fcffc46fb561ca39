#!/usr/bin/env python
"""Standalone engine process behind the Arrow-IPC front door.

Builds a Session (the chaos demo dataset with ``--demo``, or parquet
registrations via ``--table name=path``), wraps it in a QueryService
configured from the CLI flags, binds a FrontDoorServer on the requested
port (0 = ephemeral), then prints ONE machine-readable line on stdout::

    FRONTDOOR {"host": "127.0.0.1", "port": 43215, "pid": 12345, ...}

and serves until stdin reaches EOF or SIGTERM arrives.  Parent
processes (tests, the topology chaos campaign, frontdoor_bench) spawn
this script, read the FRONTDOOR line to learn the bound port, and close
the child's stdin to shut it down cleanly.

``--allow_chaos`` enables the wire ``chaos`` op so a parent can arm
FaultRegistry points (``frontdoor.drop``, ``frontdoor.kill``, ...)
inside THIS process remotely — required by the topology campaign, off
by default (a production front door must not accept fault injection).

Usage:
  python scripts/frontdoor_server.py --demo
  python scripts/frontdoor_server.py --demo --fair_queue \
      --tenant_weights interactive=4,batch=1 --preemption --query_log
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_weights(text: str) -> dict:
    """``a=2,b=1`` -> {"a": 2.0, "b": 1.0} (the --tenant_weights grammar)."""
    out = {}
    for part in (text or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition("=")
        out[name.strip()] = float(w) if w else 1.0
    return out


def build_session(args, work_dir: str):
    log_kwargs = {}
    if args.query_log:
        log_kwargs = {"query_log": True,
                      "query_log_path": os.path.join(work_dir,
                                                     "query_log.jsonl")}
    if args.demo:
        from nds_tpu.chaos import build_demo_session
        return build_demo_session(
            work_dir, chunk_rows=args.chunk_rows,
            out_of_core_min_rows=args.out_of_core_min_rows, **log_kwargs)
    from nds_tpu.config import EngineConfig
    from nds_tpu.engine import Session
    session = Session(EngineConfig(
        chunk_rows=args.chunk_rows,
        out_of_core_min_rows=args.out_of_core_min_rows, **log_kwargs))
    for spec in args.table or []:
        name, _, path = spec.partition("=")
        if not name or not path:
            raise SystemExit(f"bad --table spec: {spec!r} (want name=path)")
        session.register_parquet(name, path)
    return session


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="frontdoor_server.py", description=(
        "one engine process serving the Arrow-IPC front door"))
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 binds an ephemeral port (printed on stdout)")
    p.add_argument("--demo", action="store_true",
                   help="serve the chaos demo dataset (fact/dim/sfact)")
    p.add_argument("--table", action="append", default=[],
                   metavar="NAME=PATH", help="register a parquet table")
    p.add_argument("--allow_chaos", action="store_true",
                   help="accept the wire 'chaos' op (fault injection)")
    p.add_argument("--fair_queue", action="store_true")
    p.add_argument("--tenant_weights", default="",
                   help="per-tenant weights, e.g. interactive=4,batch=1")
    p.add_argument("--preemption", action="store_true")
    p.add_argument("--preempt_max", type=int, default=2)
    p.add_argument("--inflight_dedup", action="store_true")
    p.add_argument("--result_cache", action="store_true")
    p.add_argument("--query_log", action="store_true",
                   help="durable query log + system tables (the bench "
                        "reads p99 from system.query_log over the wire)")
    p.add_argument("--max_pending", type=int, default=512)
    p.add_argument("--dispatch_timeout_s", type=float, default=0.0)
    p.add_argument("--chunk_rows", type=int, default=8192)
    p.add_argument("--out_of_core_min_rows", type=int, default=10_000)
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="span tracer on; Chrome trace written here at "
                        "shutdown (as power --trace)")
    p.add_argument("--profile_folder", default=None,
                   help="jax.profiler trace of the whole serving time under "
                        "this folder; with --trace the spans are on it as "
                        "nds.* host events (scripts/trace_report.py "
                        "--xplane)")
    args = p.parse_args(argv)

    from nds_tpu.config import maybe_enable_compile_cache
    from nds_tpu.service import FrontDoorServer, QueryService, ServiceConfig

    # same persistent compile cache as every CLI (config.compile_cache_dir)
    maybe_enable_compile_cache()
    if args.trace:
        from nds_tpu.obs.trace import TRACER
        TRACER.configure(enabled=True)
    work_dir = tempfile.mkdtemp(prefix="frontdoor_")
    session = build_session(args, work_dir)
    rc_cfg = None
    if args.result_cache:
        from nds_tpu.engine.result_cache import ResultCacheConfig
        rc_cfg = ResultCacheConfig()
    cfg = ServiceConfig(max_pending=args.max_pending,
                        dispatch_timeout_s=args.dispatch_timeout_s,
                        fair_queue=args.fair_queue,
                        tenant_weights=parse_weights(args.tenant_weights),
                        preemption=args.preemption,
                        preempt_max=args.preempt_max,
                        inflight_dedup=args.inflight_dedup,
                        result_cache=rc_cfg)
    svc = QueryService(session, cfg)
    svc.start()
    server = FrontDoorServer(svc, host=args.host, port=args.port,
                             allow_chaos=args.allow_chaos)
    server.start()
    import jax

    from nds_tpu.report import device_capture
    print("FRONTDOOR " + json.dumps({
        "host": args.host, "port": server.port, "pid": os.getpid(),
        "epoch": server.epoch, "fair_queue": args.fair_queue,
        "preemption": args.preemption,
        # what this engine really runs on: the device as JAX reports it,
        # and whether 64-bit types are on (off = f32/i32 on the device)
        "device": device_capture(),
        "x64": bool(jax.config.jax_enable_x64)}), flush=True)

    stop = {"done": False}

    def _term(_sig, _frm):
        stop["done"] = True

    signal.signal(signal.SIGTERM, _term)
    if args.profile_folder:
        jax.profiler.start_trace(args.profile_folder)
    try:
        # serve until the parent closes our stdin (the clean-shutdown
        # handshake) or SIGTERM flips the flag
        while not stop["done"]:
            line = sys.stdin.readline()
            if not line:
                break
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        svc.close()
        if args.profile_folder:
            jax.profiler.stop_trace()
        if args.trace:
            TRACER.write_chrome_trace(args.trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
