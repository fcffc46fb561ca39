"""Per-program device-time attribution.

The engine's compiled-program boundary (``CompiledQuery.run``) is where
instrumentation must live (the Flare lesson, PAPERS.md): each dispatch is
one XLA program — a whole query, a CTE/rollup segment, or a fused morsel
group. Every run reports its measured wall time here under the program's
label, and the first compile contributes the program's static
``cost_analysis()`` FLOPs/bytes, so the registry can rank programs by
device time and compute a PER-PROGRAM roofline fraction — replacing the
single global ``roofline_frac`` with a sorted "top programs by device
time" table that names the kernel-work targets directly (ROADMAP item 1).

``device_ms`` is a HOST-clock wall around dispatch + the D2H result
transfer (run() measures around one ``device_get``): the fetch is part of
what the program costs the stream, so it belongs in the attribution, but it
is not a device-trace duration. Roofline fractions divide by the published
HBM bandwidth of the device the process runs on (``DEVICE_PEAKS``); a
device without an entry is an error, never a default.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Optional


#: Published per-chip peaks keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud TPU documentation, "TPU v5e" system architecture
#: (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM2e at 819 GB/s per chip.
DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0,
                    "int8_tops": 393.0},
}


class UnknownDeviceError(LookupError):
    """No published peaks on record for this device kind."""


def peak_hbm_gbps(device_kind: str) -> float:
    """Published HBM bandwidth (GB/s) of ``device_kind`` — the roofline
    denominator. Unknown kinds raise: a made-up bandwidth would print a
    made-up utilization."""
    try:
        return DEVICE_PEAKS[device_kind]["hbm_gbps"]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            "nds_tpu.obs.device_time.DEVICE_PEAKS with its source") from None


def roofline_bw_gbps(device: dict) -> Optional[float]:
    """Roofline denominator for a ``report.device_capture()`` record: None
    on the CPU (a host run has no device roofline — not measured), the
    published peak otherwise (an unknown accelerator raises)."""
    if device["platform"] == "cpu":
        return None
    return peak_hbm_gbps(device["device_kind"])


@dataclass
class ProgramStat:
    """Accumulated execution record of one compiled program."""
    label: str
    runs: int = 0
    device_ms: float = 0.0          # summed measured dispatch+fetch wall
    max_ms: float = 0.0
    #: the program's first (compile+run) dispatch, kept separate so
    #: steady-state means — and the rooflines derived from them — are not
    #: diluted by one-time compile cost
    first_ms: Optional[float] = None
    flops: Optional[float] = None           # per-execution, cost_analysis
    bytes_accessed: Optional[float] = None  # per-execution, cost_analysis
    extra: dict = field(default_factory=dict)

    def steady_mean_ms(self) -> float:
        """Mean over steady-state (post-first) runs; falls back to the
        overall mean when only the first run exists."""
        if self.first_ms is not None and self.runs > 1:
            return (self.device_ms - self.first_ms) / (self.runs - 1)
        return self.device_ms / self.runs if self.runs else 0.0


class ProgramRegistry:
    """Thread-safe label -> ProgramStat accumulator (compile pools and
    concurrent streams report simultaneously)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._programs: dict[str, ProgramStat] = {}

    def record_run(self, label: str, device_ms: float,
                   first: bool = False) -> None:
        with self._lock:
            st = self._programs.get(label)
            if st is None:
                st = ProgramStat(label)
                self._programs[label] = st
            st.runs += 1
            st.device_ms += device_ms
            st.max_ms = max(st.max_ms, device_ms)
            if first and st.first_ms is None:
                st.first_ms = device_ms

    def record_cost(self, label: str, cost) -> None:
        """Attach a jax ``compiled.cost_analysis()`` result (a dict; a
        backend that reports none is ignored — cost data enriches the
        table, it never fails a run)."""
        if not isinstance(cost, dict):
            return
        flops = cost.get("flops")
        bytes_accessed = cost.get("bytes accessed")
        with self._lock:
            st = self._programs.get(label)
            if st is None:
                st = ProgramStat(label)
                self._programs[label] = st
            if flops is not None:
                st.flops = float(flops)
            if bytes_accessed is not None:
                st.bytes_accessed = float(bytes_accessed)

    def total_ms(self) -> float:
        with self._lock:
            return sum(s.device_ms for s in self._programs.values())

    def table(self, bw_gbps: Optional[float] = None,
              top: Optional[int] = None) -> list[dict]:
        """Sorted (desc by total device time) per-program rows.

        ``roofline_frac`` is per program and present only when `bw_gbps`
        (``peak_hbm_gbps`` of the device the process runs on) is given: the
        fraction of that bandwidth the program's cost-analysis bytes would
        saturate over its mean measured run — the program-local version of
        the bench's global number, so the slowest-and-least-bound programs
        (the Pallas-kernel targets) sort to the top with their own
        utilization attached."""
        with self._lock:
            stats = sorted(self._programs.values(),
                           key=lambda s: s.device_ms, reverse=True)
        rows = []
        for s in stats[:top] if top else stats:
            mean_ms = s.steady_mean_ms()
            row = {
                "program": s.label,
                "runs": s.runs,
                "device_ms": round(s.device_ms, 3),
                "mean_ms": round(mean_ms, 3),
                "max_ms": round(s.max_ms, 3),
            }
            if s.first_ms is not None:
                row["first_ms"] = round(s.first_ms, 3)
            if s.flops is not None:
                row["flops"] = s.flops
            if s.bytes_accessed is not None:
                row["bytes_accessed"] = s.bytes_accessed
                if bw_gbps and mean_ms > 0:
                    ideal_s = s.bytes_accessed / (bw_gbps * 1e9)
                    row["roofline_frac"] = round(
                        ideal_s / (mean_ms / 1e3), 5)
            rows.append(row)
        return rows

    def snapshot(self) -> dict[str, ProgramStat]:
        with self._lock:
            return dict(self._programs)

    def reset(self) -> None:
        with self._lock:
            self._programs = {}


#: process-global registry; CompiledQuery.run reports into it.
PROGRAMS = ProgramRegistry()


def timed_call(fn, *args) -> tuple[float, object]:
    """One measured call of ``fn(*args)``: returns (wall ms, host result).
    JAX dispatch is asynchronous, so the clock stops only after
    ``block_until_ready``; the host copy of the result is taken outside the
    timed region."""
    import time as _time

    import jax
    t0 = _time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    ms = (_time.perf_counter() - t0) * 1000.0
    return ms, jax.device_get(out)


def coverage(table_rows: list[dict], measured_wall_ms: float) -> float:
    """Fraction of a measured wall-clock interval the per-program device
    times account for (the >=90% attribution acceptance check)."""
    if measured_wall_ms <= 0:
        return 0.0
    return sum(r["device_ms"] for r in table_rows) / measured_wall_ms


def format_table(rows: list[dict]) -> str:
    """Fixed-width text rendering of ``ProgramRegistry.table`` rows for
    stderr diagnostics / trace_report."""
    if not rows:
        return "(no programs recorded)"
    head = (f"{'program':<40} {'runs':>5} {'total_ms':>10} {'mean_ms':>9} "
            f"{'roofline':>9}")
    lines = [head, "-" * len(head)]
    for r in rows:
        rf = r.get("roofline_frac")
        lines.append(
            f"{r['program'][:40]:<40} {r['runs']:>5} {r['device_ms']:>10.1f} "
            f"{r['mean_ms']:>9.2f} "
            f"{(f'{rf:.4f}' if rf is not None else '-'):>9}")
    return "\n".join(lines)
