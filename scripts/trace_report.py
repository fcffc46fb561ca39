#!/usr/bin/env python
"""Summarize NDS-TPU observability artifacts on the terminal.

Accepts any of the formats the obs layer emits and prints the aggregate
view a Perfetto session would start from:

- Chrome trace-event JSON (``power --trace`` /
  ``service_bench.py --trace``): per-span-name rollup (count / total /
  mean / max ms) plus the slowest individual spans with their attributes;
  traces containing ``service/*`` spans additionally get a per-tenant
  rollup and a slowest-ticket listing (the ``service/ticket`` root spans
  opened at admission);
- JSONL event logs (one event per line, same rollup);
- flight-recorder JSONL dumps (``obs.flight``): per-event-type counts,
  per-tenant rollup, and the slowest completed tickets;
- bench JSON lines (the committed ``BENCH_r*.json`` records): the engine
  metrics snapshot, the span rollup, and (schema >= 3) histogram quantile
  tables;
- ``--xplane FILE``: a ``jax.profiler`` trace (``*.xplane.pb``, e.g. from
  ``power --profile_folder``): device time per program from the device's
  own clock (the ``XLA Modules`` line; plan programs are
  ``jit_nds_<query>_<unit>``) and the device's idle gaps, each split among
  the ``nds.`` spans open on the thread that dispatches (innermost first:
  self time); given the Chrome trace of the same run as
  ARTIFACT too, how far the two clocks are apart after the recorded anchor.

Usage:  python scripts/trace_report.py ARTIFACT [--top N]
        python scripts/trace_report.py --xplane FILE [ARTIFACT] [--top N]

Stdlib plus the dependency-free ``nds_tpu.obs.metrics`` (histogram
quantile math); ``--xplane`` needs jax (``nds_tpu.obs.xplane``). Safe to
point at artifacts from any round (schema_version tolerant — unknown keys
are ignored).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_events(path: str) -> list[dict] | None:
    """Trace events from a Chrome trace file or JSONL log; None when the
    file is some other JSON artifact (e.g. a bench summary)."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        events = []
        for line in text.splitlines():
            line = line.strip()
            if line:
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError as e:
                    raise ValueError(
                        f"{path}: neither JSON nor JSONL "
                        f"({e})") from None
        return events
    if isinstance(doc, dict) and "traceEvents" in doc:
        return doc["traceEvents"]
    if isinstance(doc, list):
        return doc
    return None


def rollup(events: list[dict]) -> list[dict]:
    """Per-span-name aggregate over complete (ph == "X") events."""
    agg: dict[str, dict] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        row = agg.setdefault(e["name"], {"name": e["name"], "count": 0,
                                         "total_ms": 0.0, "max_ms": 0.0})
        ms = e.get("dur", 0) / 1000.0
        row["count"] += 1
        row["total_ms"] += ms
        row["max_ms"] = max(row["max_ms"], ms)
    out = sorted(agg.values(), key=lambda r: r["total_ms"], reverse=True)
    for r in out:
        r["mean_ms"] = r["total_ms"] / r["count"] if r["count"] else 0.0
    return out


def print_rollup(rows: list[dict]) -> None:
    head = (f"{'span':<24} {'count':>7} {'total_ms':>11} {'mean_ms':>9} "
            f"{'max_ms':>9}")
    print(head)
    print("-" * len(head))
    for r in rows:
        print(f"{r['name'][:24]:<24} {r['count']:>7} {r['total_ms']:>11.1f} "
              f"{r['mean_ms']:>9.2f} {r['max_ms']:>9.1f}")


def print_slowest(events: list[dict], top: int) -> None:
    spans = sorted((e for e in events if e.get("ph") == "X"),
                   key=lambda e: e.get("dur", 0), reverse=True)[:top]
    print(f"\nslowest {len(spans)} spans:")
    for e in spans:
        args = e.get("args", {})
        label = args.get("label") or args.get("table") or ""
        detail = f" [{label}]" if label else ""
        print(f"  {e.get('dur', 0) / 1000.0:>9.1f} ms  "
              f"{e['name']}{detail}  {args}")


def print_service_view(events: list[dict], top: int) -> None:
    """Service-trace extras: per-tenant rollup over the ``service/ticket``
    root spans and the slowest tickets (label, latency, batch company)."""
    tickets = [e for e in events
               if e.get("ph") == "X" and e.get("name") == "service/ticket"]
    if not tickets:
        return
    tenants: dict[str, dict] = {}
    for e in tickets:
        t = (e.get("args") or {}).get("tenant", "?")
        row = tenants.setdefault(t, {"count": 0, "total_ms": 0.0,
                                     "max_ms": 0.0, "errors": 0})
        ms = e.get("dur", 0) / 1000.0
        row["count"] += 1
        row["total_ms"] += ms
        row["max_ms"] = max(row["max_ms"], ms)
        if (e.get("args") or {}).get("error"):
            row["errors"] += 1
    print(f"\nservice tickets by tenant ({len(tickets)} tickets):")
    head = (f"{'tenant':<16} {'tickets':>8} {'mean_ms':>9} {'max_ms':>9} "
            f"{'errors':>7}")
    print(head)
    print("-" * len(head))
    for t, r in sorted(tenants.items(), key=lambda kv: -kv[1]["max_ms"]):
        print(f"{t[:16]:<16} {r['count']:>8} "
              f"{r['total_ms'] / r['count']:>9.1f} {r['max_ms']:>9.1f} "
              f"{r['errors']:>7}")
    slow = sorted(tickets, key=lambda e: e.get("dur", 0),
                  reverse=True)[:top]
    print(f"\nslowest {len(slow)} tickets:")
    for e in slow:
        args = e.get("args", {})
        print(f"  {e.get('dur', 0) / 1000.0:>9.1f} ms  "
              f"{args.get('label', '?')}  tenant={args.get('tenant', '?')}"
              f"{'  ERROR=' + args['error'] if args.get('error') else ''}")


def is_flight_log(events: list[dict]) -> bool:
    """Flight-recorder dumps are JSONL like trace event logs but carry
    ``event``/``t_ms`` instead of Chrome's ``ph``/``ts``."""
    return bool(events) and all(
        isinstance(e, dict) and "event" in e and "ph" not in e
        for e in events)


def print_flight(events: list[dict], top: int) -> None:
    """Flight-recorder dump: event-type counts, per-tenant rollup, and
    the slowest completed tickets."""
    kinds: dict[str, int] = {}
    tenants: dict[str, dict] = {}
    for e in events:
        kinds[e["event"]] = kinds.get(e["event"], 0) + 1
        t = e.get("tenant")
        if t is None:
            continue
        row = tenants.setdefault(t, {"complete": 0, "reject": 0,
                                     "expire": 0, "error": 0,
                                     "total_ms": 0.0, "max_ms": 0.0})
        k = e["event"]
        if k in row:
            row[k] += 1
        if k == "complete" and e.get("latency_ms") is not None:
            row["total_ms"] += e["latency_ms"]
            row["max_ms"] = max(row["max_ms"], e["latency_ms"])
    span_s = (events[-1]["t_ms"] - events[0]["t_ms"]) / 1000.0 \
        if len(events) > 1 else 0.0
    print(f"flight recorder: {len(events)} events over {span_s:.1f}s")
    for k in sorted(kinds, key=lambda k: -kinds[k]):
        print(f"  {k:<10} {kinds[k]}")
    if tenants:
        head = (f"\n{'tenant':<16} {'complete':>9} {'reject':>7} "
                f"{'expire':>7} {'error':>6} {'mean_ms':>9} {'max_ms':>9}")
        print(head)
        print("-" * (len(head) - 1))
        for t, r in sorted(tenants.items(),
                           key=lambda kv: -kv[1]["max_ms"]):
            mean = r["total_ms"] / r["complete"] if r["complete"] else 0.0
            print(f"{t[:16]:<16} {r['complete']:>9} {r['reject']:>7} "
                  f"{r['expire']:>7} {r['error']:>6} {mean:>9.1f} "
                  f"{r['max_ms']:>9.1f}")
    # self-healing / lifecycle vocabulary (chaos-hardened serving): the
    # old event set prints exactly as before — this block only appears
    # when the new events are present in the dump
    trips: dict[str, int] = {}
    probes: dict[str, int] = {}
    quarantines = []
    phases = []
    for e in events:
        if e["event"] == "trip":
            r = e.get("reason", "?")
            trips[r] = trips.get(r, 0) + 1
        elif e["event"] == "probe":
            key = f"{e.get('error_class', '?')}" + \
                ("/closed" if e.get("outcome") == "closed" else "")
            probes[key] = probes.get(key, 0) + 1
        elif e["event"] == "quarantine":
            quarantines.append(e)
        elif e["event"] == "lifecycle_phase":
            phases.append(e)
    if trips or probes or quarantines:
        print("\nself-healing:")
        for r in sorted(trips, key=lambda r: -trips[r]):
            print(f"  trip {r:<24} x{trips[r]}")
        for k in sorted(probes):
            print(f"  probe {k:<23} x{probes[k]}")
        for e in quarantines:
            print(f"  quarantine fp={e.get('fp', '?')} "
                  f"strikes={e.get('strikes', '?')} "
                  f"reason={e.get('reason', '?')}")
    if phases:
        print("\nlifecycle phases:")
        for e in phases:
            extra = f" ({e['elapsed_s']}s)" if e.get("elapsed_s") else ""
            print(f"  {e['t_ms']:>10.1f} ms  {e.get('phase', '?'):<18} "
                  f"{e.get('status', '?')}{extra}")
    done = sorted((e for e in events if e["event"] == "complete"
                   and e.get("latency_ms") is not None),
                  key=lambda e: -e["latency_ms"])[:top]
    print(f"\nslowest {len(done)} tickets:")
    for e in done:
        extra = f"  batched_with={e['batched_with']}" \
            if e.get("batched_with") else ""
        print(f"  {e['latency_ms']:>9.1f} ms  {e.get('label', '?')}  "
              f"tenant={e.get('tenant', '?')}{extra}")


def print_bench(doc: dict, top: int) -> None:
    print(f"bench: {doc.get('metric')} = {doc.get('value')} "
          f"{doc.get('unit', '')} (vs_baseline {doc.get('vs_baseline')})")
    metrics = doc.get("metrics") or {}
    if metrics:
        print("\nengine metrics:")
        for name, v in metrics.items():
            if v:
                print(f"  {name:<24} {v}")
    spans = doc.get("spans") or {}
    if spans:
        rows = [{"name": n, **r,
                 "mean_ms": r["total_ms"] / r["count"] if r["count"] else 0.0}
                for n, r in spans.items()]
        rows.sort(key=lambda r: r["total_ms"], reverse=True)
        print()
        print_rollup(rows)
    hists = doc.get("histograms") or {}
    if hists:
        sys.path.insert(0, REPO)
        from nds_tpu.obs.metrics import quantile_from_snapshot
        print("\nhistograms (count / p50 / p95 / p99 / max ms):")
        for key, snap in sorted(hists.items()):
            qs = [quantile_from_snapshot(snap, p)
                  for p in (0.5, 0.95, 0.99)]
            qtxt = " ".join(f"{q:>9.1f}" if q is not None else f"{'-':>9}"
                            for q in qs)
            print(f"  {key[:48]:<48} {snap['count']:>7} {qtxt} "
                  f"{snap['max'] if snap['max'] is not None else 0:>9.1f}")


def print_xplane(path: str, chrome_trace: str | None, top: int) -> None:
    """The device's side of a run: device time per program and idle gaps
    by the innermost ``nds.`` span open on the dispatching thread
    (``xplane.idle_gaps``), both on the device trace's clock."""
    sys.path.insert(0, REPO)
    from nds_tpu.obs import xplane
    trace = xplane.read(path)
    rows = xplane.program_table(trace)
    print(f"device time by program ({len(trace['devices'])} device "
          f"plane(s), XLA Modules line):")
    head = (f"{'program':<56} {'runs':>6} {'total_ms':>10} {'mean_ms':>9} "
            f"{'max_ms':>9}")
    print(head)
    print("-" * len(head))
    for r in rows[:top]:
        print(f"{r['program'][:56]:<56} {r['runs']:>6} "
              f"{r['device_ms']:>10.1f} {r['mean_ms']:>9.2f} "
              f"{r['max_ms']:>9.2f}")
    print(f"\ndevice idle gaps by covering {xplane.ANNOTATION_PREFIX}* span "
          f"({len(trace['spans'])} host events):")
    for name, seconds in xplane.idle_gaps(trace)[:top]:
        print(f"  {seconds * 1e3:>10.1f} ms  {name}")
    if chrome_trace:
        with open(chrome_trace) as f:
            doc = json.load(f)
        check = xplane.clock_check(trace, doc["traceEvents"], doc["clock"])
        print(f"\nspans against their host events after the anchor: {check}")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="trace_report.py")
    p.add_argument("artifact", nargs="?",
                   help="Chrome trace / JSONL event log / bench JSON")
    p.add_argument("--xplane", default=None, metavar="FILE",
                   help="a jax.profiler *.xplane.pb: device time per "
                        "program, idle gaps by nds.* span")
    p.add_argument("--top", type=int, default=10,
                   help="rows in the slowest-spans / top-programs tables")
    a = p.parse_args(argv)
    if a.xplane:
        print_xplane(a.xplane, a.artifact, a.top)
        return 0
    if not a.artifact:
        p.error("an ARTIFACT or --xplane FILE is required")
    try:
        events = load_events(a.artifact)
        if events is not None and is_flight_log(events):
            print_flight(events, a.top)
            return 0
        if events is not None and events and \
                all(isinstance(e, dict) and "ph" in e for e in events):
            print_rollup(rollup(events))
            print_slowest(events, a.top)
            print_service_view(events, a.top)
            return 0
        with open(a.artifact) as f:
            doc = json.load(f)
    except (ValueError, OSError) as e:
        print(f"trace_report: {e}", file=sys.stderr)
        return 2
    if isinstance(doc, dict):
        print_bench(doc, a.top)
        return 0
    print(f"unrecognized artifact format: {a.artifact}", file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
