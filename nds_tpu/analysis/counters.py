"""ENG006 — counter discipline: metrics, glossary, and gate stay in sync.

The metrics contract has three legs that historically drifted apart by
hand-editing:

1. **Glossary.** Every ``METRICS.counter/gauge/histogram("name", ...)``
   declaration must carry non-empty help text — ``describe()`` is the
   operator-facing glossary, and a help-less metric is invisible there.
2. **Write sites resolve.** Every ``SOME_CONST.inc()/dec()/add()/set()/
   observe()`` through an ALL_CAPS constant must resolve to a metric
   declaration somewhere in the tree — a renamed declaration leaves the
   old write sites incrementing a constant that no longer exists (an
   ImportError at best, a silently re-registered orphan at worst).
3. **Gate cross-check, both directions.** Every name in
   ``scripts/metrics_gate.py``'s ``STRICT_ZERO`` tuple and every key in
   ``cicd/metrics_baseline.json``'s ``gated`` dict must name a metric
   that still exists (orphan gate rows assert about nothing); and every
   gate-shaped declaration (counter/gauge whose name is not
   report-only) must have a baseline row (a new counter nobody baselines
   is a regression the gate cannot catch).

``# lint: counter-exempt (<reason>)`` on the write site / declaration
line is the audited escape hatch.
"""
from __future__ import annotations

import ast
import json
import os

from .base import Finding, has_pragma, suggestion_for
from .summary import ProgramSummary

#: ALL_CAPS constants whose inc/add/set/observe-shaped methods are NOT
#: metric writes (trackers/recorders that share the verb vocabulary)
NON_METRIC_CONSTS = frozenset({
    "DEVICE_MEM", "FLIGHT", "TRACER", "METRICS",
})

#: fallback when the gate module cannot be parsed for its own constant
DEFAULT_REPORT_ONLY_SUFFIXES = ("_ms", "_bytes", "bytes_uploaded",
                                "bytes_fetched", "tight_morsel_replays",
                                "mask_carried_filters", "bytes_decoded")


def _gate_artifacts(root: str | None):
    """(gate_py, baseline_json) paths when both exist under ``root``."""
    if not root:
        return None, None
    gate = os.path.join(root, "scripts", "metrics_gate.py")
    base = os.path.join(root, "cicd", "metrics_baseline.json")
    if os.path.isfile(gate) and os.path.isfile(base):
        return gate, base
    return None, None


def _parse_gate(gate_path: str):
    """(STRICT_ZERO [(name, line)], REPORT_ONLY_SUFFIXES) from the gate
    module's AST — the gate file is data here, never imported."""
    strict: list[tuple[str, int]] = []
    suffixes = DEFAULT_REPORT_ONLY_SUFFIXES
    try:
        with open(gate_path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=gate_path)
    except (OSError, SyntaxError):
        return strict, suffixes
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        names = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if not names or not isinstance(node.value, (ast.Tuple, ast.List,
                                                    ast.Set)):
            continue
        vals = [(e.value, e.lineno) for e in node.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)]
        if "STRICT_ZERO" in names:
            strict = vals
        elif "REPORT_ONLY_SUFFIXES" in names and vals:
            suffixes = tuple(v for v, _ in vals)
    return strict, suffixes


def check_counters(prog: ProgramSummary, root: str | None) -> list[Finding]:
    findings: list[Finding] = []
    sug = suggestion_for("ENG006")
    decls = {}                              # metric name -> (decl, module)
    consts: set[str] = set()                # CONST bindings of declarations
    for m in prog.modules:
        for d in m.metric_decls:
            decls.setdefault(d.name, (d, m))
            if d.const:
                consts.add(d.const)

    # 1. glossary: every metric FAMILY carries help somewhere (labeled-
    #    child lookups like ``METRICS.histogram("x", tenant=t)`` inherit
    #    the family help, so help is a per-name property, not per-site)
    family_help = {}
    for m in prog.modules:
        for d in m.metric_decls:
            family_help[d.name] = family_help.get(d.name, False) or \
                d.has_help
    for m in prog.modules:
        for d in m.metric_decls:
            if family_help.get(d.name):
                continue
            findings.append(Finding(
                m.path, d.line, 0, "ENG006",
                f"metric '{d.name}' declared without help text: "
                "METRICS.describe() is the operator glossary and must "
                "cover every registered series",
                suggestion=sug,
                suppressed=has_pragma(m.lines, d.line, "counter-exempt")))

    # 2. write sites resolve to a live declaration
    for m in prog.modules:
        for u in m.metric_uses:
            if u.const in consts or u.const in NON_METRIC_CONSTS:
                continue
            findings.append(Finding(
                m.path, u.line, 0, "ENG006",
                f"metric write '{u.const}.{u.method}()' does not resolve "
                "to any METRICS declaration in the tree — the constant "
                "was renamed/removed, or this tracker belongs in the "
                "checker stoplist",
                suggestion=sug, suppressed=u.exempt))

    # 3. gate cross-check (only when the tree ships the gate artifacts)
    gate_py, baseline_json = _gate_artifacts(root)
    if gate_py is None:
        return findings
    strict_zero, suffixes = _parse_gate(gate_py)
    for name, line in strict_zero:
        if name in decls:
            continue
        findings.append(Finding(
            gate_py, line, 0, "ENG006",
            f"orphan STRICT_ZERO row '{name}': no metric with that name "
            "is declared anywhere in the tree — the gate asserts about "
            "nothing"))
    try:
        with open(baseline_json, encoding="utf-8") as fh:
            gated = json.load(fh).get("gated", {})
    except (OSError, ValueError):
        gated = {}
    for name in sorted(gated):
        if name in decls:
            continue
        findings.append(Finding(
            baseline_json, 0, 0, "ENG006",
            f"orphan baseline row '{name}': no metric with that name is "
            "declared anywhere in the tree"))
    for name, (d, m) in sorted(decls.items()):
        if d.kind not in ("counter", "gauge"):
            continue                        # histograms are report-only
        if any(name.endswith(s) for s in suffixes):
            continue
        if name in gated:
            continue
        findings.append(Finding(
            m.path, d.line, 0, "ENG006",
            f"metric '{name}' ({d.kind}) has no cicd/metrics_baseline."
            "json row: gate-shaped series must be baselined or the "
            "regression gate cannot see them drift",
            suggestion=sug,
            suppressed=has_pragma(m.lines, d.line, "counter-exempt")))
    return findings
