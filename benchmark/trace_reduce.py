"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device busy time, the
device operations that took most of it, and the idle gaps named by what the
host was doing. Reads with ``jax.profiler.ProfileData`` only.

On a TPU every chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation that ran and ``XLA Modules`` one per program;
the host is the plane ``/host:CPU`` with one line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names. Busy
time is the union of the ``XLA Ops`` intervals (of ``XLA Modules`` where a
trace has no op line), averaged over the chips. A gap is named by the
harness annotation (``bench.*``) that covers most of it; the two clocks
agree to about a millisecond, which is enough for gaps worth listing.
"""
from __future__ import annotations

import bisect
import re

ANNOTATION_PREFIX = "bench."
SLICE_ANNOTATION = "bench.slice"


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _op_label(name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%").strip() or name


def _module_label(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def read_planes(path: str):
    """(device planes, host annotations): per device plane the lists of
    (start_ns, end_ns, name) of its op and module lines; annotations as
    (start_ns, end_ns, name) over all host threads."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, notes = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") or \
                plane.name.startswith("/device:GPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    lines[line.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
            if lines:
                devices.append(lines)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        notes.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return devices, notes


def reduce_planes(devices: list, notes: list, window_s: float) -> dict:
    """The reduction proper, on plain lists (see ``read_planes``).

    Returns ``busy_s`` (mean over chips of the union of op intervals),
    ``window_s`` as given (the harness's clock around the traced slice),
    ``idle_pct``, ``device_ops`` [[module/op, seconds]] and ``idle_gaps``
    [[host annotation, seconds]], both sorted by seconds, largest first.
    Nothing ran on a device: ``busy_s`` 0 and empty lists."""
    busy_ns = 0.0
    op_ns: dict = {}
    gap_ns: dict = {}
    sl = [n for n in notes if n[2] == SLICE_ANNOTATION]
    named = sorted(n for n in notes if n[2] != SLICE_ANNOTATION)
    for lines in devices:
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        mods = sorted(lines.get("XLA Modules") or [])
        mod_starts = [m[0] for m in mods]
        for s, e, name in ops:
            label = _op_label(name)
            i = bisect.bisect_right(mod_starts, s) - 1
            if "XLA Ops" in lines and i >= 0 and s < mods[i][1]:
                label = _module_label(mods[i][2]) + "/" + label
            elif "XLA Ops" not in lines:
                label = _module_label(name)
            op_ns[label] = op_ns.get(label, 0.0) + (e - s)
        merged = _union([(s, e) for s, e, _ in ops])
        busy_ns += sum(e - s for s, e in merged)
        if not merged:
            continue
        lo, hi = (sl[0][0], sl[0][1]) if sl else (merged[0][0],
                                                  merged[-1][1])
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                who = _covering(named, g0, g1)
                gap_ns[who] = gap_ns.get(who, 0.0) + (g1 - g0)
    n = max(len(devices), 1)
    busy_s = busy_ns / n / 1e9
    rank = lambda d: [[k, v / n / 1e9] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])]
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s)
            if window_s > 0 else None,
            "device_ops": rank(op_ns), "idle_gaps": rank(gap_ns)}


def _covering(named: list, g0: float, g1: float) -> str:
    """The annotation that overlaps [g0, g1) most (innermost on a tie:
    the later start), or ``unannotated``."""
    best, best_ov = "unannotated", 0.0
    for s, e, name in named:
        if s >= g1:
            break
        ov = min(e, g1) - max(s, g0)
        if ov > 0 and ov >= best_ov:
            best, best_ov = name, ov
    return best


def reduce_file(path: str, window_s: float) -> dict:
    devices, notes = read_planes(path)
    return reduce_planes(devices, notes, window_s)
