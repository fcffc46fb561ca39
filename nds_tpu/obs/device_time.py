"""Published device peaks: the denominators of a roofline share.

Device time itself is read from the device's own clock: a ``jax.profiler``
trace names every program on its ``XLA Modules`` line
(``jit_nds_<query>_<unit>``, ``executor.program_name``) and
``nds_tpu.obs.xplane`` / ``scripts/trace_report.py --xplane`` reduce it to
device time per program and idle gaps by covering ``nds.`` span. The host's
wall around one dispatch stays in ``ExecStats.device_ms`` under that
description; it is no device-trace duration and feeds no roofline.

Roofline fractions divide by the published HBM bandwidth of the device the
process runs on (``DEVICE_PEAKS``); a device without an entry is an error,
never a default.
"""
from __future__ import annotations

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
