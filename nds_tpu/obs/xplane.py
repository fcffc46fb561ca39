"""A ``jax.profiler`` trace (``*.xplane.pb``) under the program's own names.

The device's side of what the span tracer sees from the host: every plan
program is an HLO module ``jit_nds_<query>_<unit>`` (``executor.
program_name``), so the ``XLA Modules`` line of each device plane gives
device time per program on the device's own clock; and while the tracer is
on every span is a host event ``nds.<span>[:<label>]``, so an idle gap of
the device can be named by the span that covers it. Reads with
``jax.profiler.ProfileData`` only.

Times in the file count from the profiling session's start; the ``Task
Environment`` plane states that start on the wall clock
(``profile_start_time``, ns), which is how the tracer's exports
(``clock.epoch_unix_s``) are laid over it.
"""
from __future__ import annotations

import re
from typing import Optional

from .trace import ANNOTATION_PREFIX


def read(path: str) -> dict:
    """``{"start_unix_ns", "devices", "spans"}``: per device plane the
    (start_ns, end_ns, name) events of its ``XLA Modules`` and ``XLA Ops``
    lines, and the ``nds.`` host events of every host thread."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: dict = {"start_unix_ns": None, "devices": [], "spans": []}
    for plane in data.planes:
        if plane.name == "Task Environment":
            out["start_unix_ns"] = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith(("/device:TPU:", "/device:GPU:")):
            lines = {line.name: [(e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Modules", "XLA Ops")}
            if lines:
                out["devices"].append(lines)
        elif plane.name == "/host:CPU":
            out["spans"].extend(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines for e in line.events
                if e.name.startswith(ANNOTATION_PREFIX))
    return out


def program_table(trace: dict) -> list[dict]:
    """Device time per program from the ``XLA Modules`` lines, largest
    first: ``{program, runs, device_ms, mean_ms, max_ms}``. With several
    chips a run counts once per chip it ran on."""
    rows: dict = {}
    for lines in trace["devices"]:
        for start, end, name in lines.get("XLA Modules", ()):
            program = re.sub(r"\(\d+\)$", "", name)
            row = rows.setdefault(program, {"program": program, "runs": 0,
                                            "device_ms": 0.0, "max_ms": 0.0})
            ms = (end - start) / 1e6
            row["runs"] += 1
            row["device_ms"] += ms
            row["max_ms"] = max(row["max_ms"], ms)
    out = sorted(rows.values(), key=lambda r: -r["device_ms"])
    for row in out:
        row["mean_ms"] = row["device_ms"] / row["runs"]
    return out


def _busy(lines: dict) -> list:
    """Merged [start, end] intervals in which the device ran something."""
    merged: list = []
    events = lines.get("XLA Ops") or lines.get("XLA Modules") or ()
    for start, end, _name in sorted(events):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def idle_gaps(trace: dict) -> list[list]:
    """[[span, seconds]], largest first: the device's idle time between
    its first and its last operation, each gap under the ``nds.`` span
    that covers most of it (the innermost on a tie), ``unannotated`` where
    none does. Mean over the chips."""
    spans = sorted(trace["spans"])
    gaps: dict = {}
    for lines in trace["devices"]:
        busy = _busy(lines)
        for (_, g0), (g1, _) in zip(busy, busy[1:]):
            who, best = "unannotated", 0.0
            for start, end, name in spans:
                if start >= g1:
                    break
                overlap = min(end, g1) - max(start, g0)
                if overlap > 0 and overlap >= best:
                    who, best = name, overlap
            gaps[who] = gaps.get(who, 0.0) + (g1 - g0)
    n = max(len(trace["devices"]), 1)
    return [[k, v / n / 1e9]
            for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])]


def clock_check(trace: dict, events: list, clock: dict) -> Optional[dict]:
    """How well the tracer's events, shifted by their recorded anchor, sit
    on the profile's ``nds.`` host events: ``{matched, max_start_ms,
    max_dur_ms}`` over every host event that has a tracer event of its
    name (the nearest in time), None where the file states no start."""
    if trace["start_unix_ns"] is None:
        return None
    origin_ns = clock["epoch_unix_s"] * 1e9 - trace["start_unix_ns"]
    mine: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        label = (e.get("args") or {}).get("label")
        name = ANNOTATION_PREFIX + e["name"] + (f":{label}" if label else "")
        mine.setdefault(name, []).append(
            (origin_ns + e["ts"] * 1e3, e["dur"] * 1e3))
    matched, d_start, d_dur = 0, 0.0, 0.0
    for start, end, name in trace["spans"]:
        cands = mine.get(name)
        if not cands:
            continue
        s, d = min(cands, key=lambda c: abs(c[0] - start))
        matched += 1
        d_start = max(d_start, abs(s - start) / 1e6)
        d_dur = max(d_dur, abs(d - (end - start)) / 1e6)
    return {"matched": matched, "max_start_ms": d_start,
            "max_dur_ms": d_dur}
