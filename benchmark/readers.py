"""Per-layer metrics: what a run observed, and the few kinds of reader that
turn it into a number. A metric is ``layer_metrics/<name>.json`` — its
``reader`` kind and ``args`` — so a later PR adds one as a file; a reader
that finds nothing to read returns None and the metric is left out of the
line (never a 0 for a share of a peak).
"""
from __future__ import annotations

import json
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))


class Observations:
    """Everything the traced run of one cell collected."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.clocks: dict = {}          # harness clocks, seconds
        self.spans: list = []           # the program's TRACER events
        self.window_ts = (0.0, 0.0)     # the window on the TRACER's clock, us
        self.counters: dict = {}        # METRICS delta over the window
        self.tickets: list = []         # served: per-request stage times
        self.trace_summary: dict | None = None   # trace_reduce's result
        self.slice_work = 0             # passes/requests inside the slice
        self.scan_reads: list = []      # refdata.Warehouse.reads
        self.window = None              # drivers.Window
        self.end_to_end: dict = {}      # what the window's driver computed
        self.device_kind = ""

    def per(self, what: str | None, in_slice: bool = False) -> float | None:
        if what is None:
            return 1.0
        n = self.slice_work if in_slice else self.window.work
        return float(n) if n else None


def _union_us(events: list) -> float:
    total, end = 0.0, None
    for s, d in sorted((e["ts"], e["dur"]) for e in events):
        if end is None or s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def span_sum(obs: Observations, spans: list, phase: str, scale: str,
             per: str | None = None):
    """Union of the named spans' intervals before (``setup``) or inside
    (``window``) the window, in ``s`` or ``ms``, per pass or request."""
    w0, w1 = obs.window_ts
    picked = [e for e in obs.spans if e.get("ph") == "X"
              and e["name"] in spans
              and ((e["ts"] < w0) if phase == "setup"
                   else (w0 <= e["ts"] < w1))]
    n = obs.per(per)
    if not picked or not n:
        return None
    return _union_us(picked) / (1e6 if scale == "s" else 1e3) / n


def span_table(obs: Observations) -> dict:
    """{span name: [count and total ms before the window, inside it]} — for
    the reader of a traced run's earlier lines, not for a metric."""
    w0, w1 = obs.window_ts
    out: dict = {}
    for e in obs.spans:
        if e.get("ph") != "X":
            continue
        row = out.setdefault(e["name"], [0, 0.0, 0, 0.0])
        i = 0 if e["ts"] < w0 else 2
        row[i] += 1
        row[i + 1] = round(row[i + 1] + e["dur"] / 1e3, 1)
    return out


def counter(obs: Observations, name: str, per: str | None = None,
            divide: float = 1.0, absent_is_zero: bool = False):
    n = obs.per(per)
    if name not in obs.counters and not absent_is_zero:
        return None
    return obs.counters.get(name, 0) / divide / n if n else None


def clock(obs: Observations, name: str):
    return obs.clocks.get(name)


def ticket_p50(obs: Observations, field: str):
    vals = [t[field] for t in obs.tickets if t.get(field) is not None]
    return statistics.median(vals) if vals else None


def scan_bytes_per_pass(obs: Observations) -> int:
    """One pass runs every unit once: the bytes its scans must read."""
    return sum(nbytes for _unit, _table, nbytes in obs.scan_reads)


def trace(obs: Observations, what: str, per: str | None = None):
    """``busy_ms`` per pass or request, ``idle_pct``, or
    ``scan_roofline_pct``: the least time the pass's scans could take at the
    peak memory bandwidth over the time the device was busy in a pass."""
    ts = obs.trace_summary
    if not ts or ts["busy_s"] <= 0:
        return None
    if what == "idle_pct":
        if not 0.0 <= ts["idle_pct"] <= 100.0:
            raise ValueError(f"idle share {ts['idle_pct']:.1f}% is no share")
        return ts["idle_pct"]
    n = obs.per(per, in_slice=True)
    if not n:
        return None
    if what == "busy_ms":
        return ts["busy_s"] * 1e3 / n
    if what == "scan_roofline_pct":
        from benchmark.run import peak_for
        peak = peak_for(obs.device_kind)["hbm_bytes_per_s"]
        least_s = scan_bytes_per_pass(obs) / peak
        share = 100.0 * least_s / (ts["busy_s"] / n)
        if share > 100.0:
            # never clamped: the bytes are counted too high, or the busy
            # time leaves out part of the work
            raise ValueError(f"scan roofline share {share:.1f}% is over 100%")
        return share if least_s else None
    raise ValueError(f"trace reader: unknown quantity {what!r}")


def end_to_end(obs: Observations, name: str):
    """A number of the window's own arithmetic that stands among the layer
    metrics (a tail too unsteady for a bound, beside its median)."""
    return obs.end_to_end.get(name)


READERS = {"end_to_end": end_to_end, "span_sum": span_sum, "counter": counter, "clock": clock,
           "ticket_p50": ticket_p50, "trace": trace}


def load_metric(name: str) -> dict:
    path = os.path.join(HERE, "layer_metrics", name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no layer_metrics/{name}.json")
    with open(path) as f:
        return json.load(f)


def read_all(names: list, obs: Observations) -> dict:
    out = {}
    for name in names:
        spec = load_metric(name)
        if spec["reader"] not in READERS:
            raise SystemExit(f"benchmark: {name}: no reader "
                             f"{spec['reader']!r}")
        value = READERS[spec["reader"]](obs, **spec.get("args", {}))
        if value is not None:
            out[name] = value
    return out
