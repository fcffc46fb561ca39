"""A ``jax.profiler`` trace (``*.xplane.pb``) under the program's own names.

The device's side of what the span tracer sees from the host: every plan
program is an HLO module ``jit_nds_<query>_<unit>`` (``executor.
program_name``), so the ``XLA Modules`` line of each device plane gives
device time per program on the device's own clock; and while the tracer is
on every span is a host event ``nds.<span>[:<label>]`` on the thread that
opened it, so an idle gap of the device can be split among the spans that
were open, on the thread that dispatches, while it lasted. Reads with
``jax.profiler.ProfileData`` only.

Times in the file count from the profiling session's start; the ``Task
Environment`` plane states that start on the wall clock
(``profile_start_time``, ns), which is how the tracer's exports
(``clock.epoch_unix_s``) are laid over it.
"""
from __future__ import annotations

import bisect
import re
from typing import Optional

from .trace import ANNOTATION_PREFIX

#: the spans that hand a program to the device: the thread that opens the
#: next one is the thread the device is waiting for
DISPATCH_SPANS = (ANNOTATION_PREFIX + "exec.wait",
                  ANNOTATION_PREFIX + "collective")
#: how far before the device starts a program its dispatch may have begun
#: and still count as "the next dispatch" (the clocks agree to about this)
DISPATCH_SLACK_NS = 1_000_000


def read(path: str) -> dict:
    """``{"start_unix_ns", "devices", "spans"}``: per device plane the
    (start_ns, end_ns, name) events of its ``XLA Modules`` and ``XLA Ops``
    lines, and the ``nds.`` host events as (start_ns, end_ns, name, thread)
    — ``thread`` is the host line's index, one line a thread."""
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
                (e.start_ns, e.start_ns + e.duration_ns, e.name, thread)
                for thread, line in enumerate(plane.lines)
                for e in line.events
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


def _innermost(spans: list) -> list:
    """One thread's (start, end, name) spans as disjoint segments, sorted,
    each under the innermost span open over it: a span keeps its self
    time, what its children cover goes to them."""
    out: list = []
    stack: list = []        # (end, name) of the open spans, outermost first
    at = 0

    def close(upto) -> None:
        nonlocal at
        if upto > at:
            out.append((at, upto, stack[-1][1]))
            at = upto

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= start:
            close(stack[-1][0])
            stack.pop()
        if stack:
            close(start)
        at = max(at, start)
        stack.append((end, name))
    while stack:
        close(stack[-1][0])
        stack.pop()
    return out


def idle_gaps(trace: dict) -> list[list]:
    """[[span, seconds]], largest first: the device's idle time between
    its first and its last operation, split among the ``nds.`` spans of the
    thread that dispatches. A gap's dispatching thread is the thread of the
    first ``nds.exec.wait`` or ``nds.collective`` that starts no earlier
    than a millisecond before the gap ends (of the last one where none
    follows); each instant of the gap goes to the innermost span open on
    that thread at that instant, ``unannotated`` where none is. Spans of
    other threads take nothing: the device is not waiting for them. Mean
    over the chips."""
    by_thread: dict = {}
    dispatches = []
    for start, end, name, thread in trace["spans"]:
        by_thread.setdefault(thread, []).append((start, end, name))
        if name.split(":", 1)[0] in DISPATCH_SPANS:
            dispatches.append((start, thread))
    dispatches.sort()
    dispatch_starts = [d[0] for d in dispatches]
    flat = {t: _innermost(spans) for t, spans in by_thread.items()}
    flat_starts = {t: [seg[0] for seg in segs] for t, segs in flat.items()}
    gaps: dict = {}
    for lines in trace["devices"]:
        busy = _busy(lines)
        for (_, g0), (g1, _) in zip(busy, busy[1:]):
            left = g1 - g0
            if dispatches:
                at = bisect.bisect_left(dispatch_starts,
                                        g1 - DISPATCH_SLACK_NS)
                thread = dispatches[min(at, len(dispatches) - 1)][1]
                segs = flat[thread]
                i = max(bisect.bisect_right(flat_starts[thread], g0) - 1, 0)
                while i < len(segs) and segs[i][0] < g1:
                    start, end, name = segs[i]
                    overlap = min(end, g1) - max(start, g0)
                    if overlap > 0:
                        gaps[name] = gaps.get(name, 0.0) + overlap
                        left -= overlap
                    i += 1
            if left > 0:
                gaps["unannotated"] = gaps.get("unannotated", 0.0) + left
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
    for start, end, name, _thread in trace["spans"]:
        cands = mine.get(name)
        if not cands:
            continue
        s, d = min(cands, key=lambda c: abs(c[0] - start))
        matched += 1
        d_start = max(d_start, abs(s - start) / 1e6)
        d_dur = max(d_dur, abs(d - (end - start)) / 1e6)
    return {"matched": matched, "max_start_ms": d_start,
            "max_dur_ms": d_dur}
