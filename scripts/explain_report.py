#!/usr/bin/env python
"""EXPLAIN ANALYZE renderer: annotated plan trees from profile artifacts.

The profiling layer (nds_tpu/obs/profile.py) serializes every profiled
execution as a PlanProfile JSON — ``power --explain`` writes one per
query under ``<json_summary_folder>/explain/``, tests and notebooks call
``Session.explain_analyze(...).to_dict()`` directly, and the service
exposes ``QueryService.explain_analyze`` live. This tool re-renders any
of those offline:

- a profile dump (``{"profile_version": 1, "nodes": {...}, ...}``) or a
  directory of them: the annotated tree (per-node self wall + time%,
  rows est->act, output bytes), the cardinality-audit findings, and the
  device-memory watermark line;
- a power JSON summary (``powerRunReport``): the per-query
  ``node_stats`` actual-row tables and memory watermarks the normal
  (unprofiled) runs recorded for free;
- a bench JSON: its ``memory`` block.

Pure stdlib + nds_tpu.obs.profile (no jax import on the render path).

``--audit`` flips the tool from per-artifact rendering to a CROSS-RUN
rollup: every artifact's per-node actuals (profile dumps' est->act
pairs, power summaries' and query-log JSONLs' ``node_stats`` maps) merge
into one table ranked by capacity overprovision — the bucket-drift
factor between what a schedule provisioned (the static estimate, or the
``--chunk_rows`` morsel bucket for streamed nodes) and the LARGEST
actual any run observed.

Usage:
  python scripts/explain_report.py summary/explain/query9.json
  python scripts/explain_report.py summary/explain/          # every query
  python scripts/explain_report.py summary/power_*.json      # node_stats
  python scripts/explain_report.py BENCH_r05.json            # memory block
  python scripts/explain_report.py --audit summary/explain/ qlog.jsonl \
      --chunk_rows 262144                                    # rollup
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from nds_tpu.obs.profile import PlanProfile  # noqa: E402


def _expand(paths: list[str]) -> list[str]:
    out: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            out.extend(sorted(glob.glob(os.path.join(p, "*.json"))))
        else:
            out.append(p)
    return out


def _fmt_mem(block: dict) -> str:
    def mb(k):
        v = block.get(k)
        return f"{v / (1 << 20):.1f}MB" if v is not None else "-"
    line = (f"memory: live {mb('device_live_bytes')}, "
            f"peak {mb('device_peak_bytes')}")
    if block.get("budget_bytes"):
        line += (f", headroom {mb('headroom_bytes')} of "
                 f"{mb('budget_bytes')} budget")
    return line


def render_power_summary(doc: dict, path: str) -> None:
    """Per-query node_stats tables from a power JSON summary: the actual
    row counts the normal compiled/streamed runs attribute for free
    (ExecStats.node_stats; exact per-node coverage needs --explain)."""
    stats = doc.get("execStats") or []
    name = doc.get("appName") or os.path.basename(path)
    for st in stats:
        rows = st.get("node_stats")
        print(f"{name}: mode={st.get('mode', '?')}", end="")
        for k in ("mem_peak_bytes", "mem_live_bytes"):
            if st.get(k) is not None:
                print(f" {k.replace('mem_', '')}="
                      f"{st[k] / (1 << 20):.1f}MB", end="")
        print()
        if not rows:
            print("  (no node_stats recorded — run with --explain for "
                  "full per-node coverage)")
            continue
        for lbl, n in sorted(rows.items(), key=lambda kv: -kv[1]):
            print(f"  {lbl:<28} rows {n}")


# the engine's capacity ladder (jax_backend/device.bucket), mirrored so
# the audit stays importable without jax on the render path
_CAP_LADDER_MIN = 4 << 20


def _bucket(n, minimum: int = 8) -> int:
    c = max(int(n), minimum)
    p = 1 << (c - 1).bit_length()
    if p > _CAP_LADDER_MIN:
        mid = 3 * (p >> 2)
        if c <= mid:
            return mid
    return p


def _audit_collect(doc, path: str, rollup: dict) -> None:
    """Merge one artifact's per-node observations into the rollup:
    {(template, node): {"est": static estimate or None, "act": max
    actual, "runs": sightings}}. Profile dumps carry est->act pairs;
    power summaries and query-log rows carry actuals only."""
    def feed(template, node, est, act):
        if act is None:
            return
        key = (template or "?", node)
        e = rollup.setdefault(key, {"est": None, "act": 0, "runs": 0})
        e["act"] = max(e["act"], int(act))
        e["runs"] += 1
        if est is not None:
            e["est"] = int(est)

    if isinstance(doc, dict) and "nodes" in doc and \
            ("profile_version" in doc or "root" in doc):
        label = doc.get("label") or \
            os.path.splitext(os.path.basename(path))[0]
        for node, ns in doc["nodes"].items():
            feed(label, node, ns.get("est_rows"), ns.get("rows"))
        return
    if isinstance(doc, dict) and "execStats" in doc:
        app = (doc.get("env") or {}).get("appName") or \
            doc.get("appName") or os.path.basename(path)
        for i, st in enumerate(doc["execStats"]):
            label = st.get("label") or \
                (app if len(doc["execStats"]) == 1 else f"{app}#{i}")
            for node, act in (st.get("node_stats") or {}).items():
                feed(label, node, None, act)
        return
    if isinstance(doc, list):          # query-log JSONL rows
        for r in doc:
            ns = r.get("node_stats")
            if isinstance(ns, str):
                try:
                    ns = json.loads(ns)
                except json.JSONDecodeError:
                    continue
            for node, act in (ns or {}).items():
                feed(r.get("label") or r.get("template"), node, None, act)


def render_audit(rollup: dict, chunk_rows, top: int) -> None:
    """The ranked overprovision table: per (template, node), the bucket
    the schedule provisioned (static estimate, or the --chunk_rows
    morsel bucket when only actuals are known) vs the bucket the worst
    observed actual needs — factor = provisioned/needed. Scans are
    skipped in the chunk_rows fallback (the morsel IS the scan)."""
    findings = []
    for (template, node), e in rollup.items():
        est = e["est"]
        if est is None:
            if not chunk_rows or node.startswith("ScanNode"):
                continue
            est = int(chunk_rows)
        prov, need = _bucket(est), _bucket(e["act"])
        if prov > need:
            findings.append((prov / need, template, node, est, e))
    findings.sort(key=lambda f: (-f[0], f[1], f[2]))
    if not findings:
        print("audit: no overprovisioned nodes found")
        return
    print(f"audit: {len(findings)} overprovisioned node(s) across "
          f"{len({t for _, t, *_ in findings})} template(s) "
          "(provisioned bucket / needed bucket)")
    print(f"{'factor':>9}  {'template':<16} {'node':<28} "
          f"{'prov':>10} {'actual':>10} {'runs':>5}")
    for factor, template, node, est, e in findings[:top]:
        print(f"{factor:>8.0f}x  {template:<16} {node:<28} "
              f"{_bucket(est):>10} {e['act']:>10} {e['runs']:>5}")
    if len(findings) > top:
        print(f"... {len(findings) - top} more (raise --findings)")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="explain_report.py", description=(
        "render EXPLAIN ANALYZE profiles (annotated plan tree + "
        "cardinality audit + memory watermarks) from profile dumps, "
        "power summaries, or bench JSON"))
    p.add_argument("artifacts", nargs="+",
                   help="profile JSON(s), a directory of them (power "
                        "--explain writes <summary>/explain/), power "
                        "JSON summaries, or a bench JSON")
    p.add_argument("--findings", type=int, default=8,
                   help="cardinality-audit findings shown per profile "
                        "(with --audit: rollup rows shown)")
    p.add_argument("--audit", action="store_true",
                   help="cross-run rollup instead of per-artifact "
                        "rendering: merge every artifact's per-node "
                        "actuals and print the ranked overprovision "
                        "list (bucket-drift factor, worst first)")
    p.add_argument("--chunk_rows", type=int, default=0,
                   help="with --audit: the streamed morsel bound the "
                        "run provisioned capacity buckets from — lets "
                        "actuals-only sources (node_stats maps, query "
                        "logs) estimate the ladder gap on streamed "
                        "non-scan nodes")
    a = p.parse_args(argv)
    paths = _expand(a.artifacts)
    if not paths:
        print("explain_report: no artifacts found", file=sys.stderr)
        return 2
    rc = 0
    rollup: dict = {}
    for i, path in enumerate(paths):
        if not a.audit and i:
            print()
        try:
            with open(path) as f:
                if path.endswith(".jsonl"):
                    doc = [json.loads(line) for line in f if line.strip()]
                else:
                    doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"explain_report: {path}: {e}", file=sys.stderr)
            rc = 2
            continue
        if a.audit:
            _audit_collect(doc, path, rollup)
            continue
        if not isinstance(doc, dict):
            print(f"explain_report: {path}: not a JSON object",
                  file=sys.stderr)
            rc = 2
            continue
        if "nodes" in doc and ("profile_version" in doc or "root" in doc):
            print(PlanProfile.from_dict(doc).render(
                top_findings=a.findings))
        elif "execStats" in doc:
            render_power_summary(doc, path)
        elif "memory" in doc:
            print(f"{os.path.basename(path)}: {_fmt_mem(doc['memory'])}")
        else:
            print(f"explain_report: {path}: no profile, execStats, or "
                  "memory block", file=sys.stderr)
            rc = 2
    if a.audit:
        render_audit(rollup, a.chunk_rows, max(a.findings, 1))
    return rc


if __name__ == "__main__":
    sys.exit(main())
