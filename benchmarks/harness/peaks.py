"""Published peaks, keyed by ``device_kind``. A device that is not in the
table is an error, never a default: a share of an assumed peak means nothing.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip)."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


class UnknownDevice(Exception):
    """``device_kind`` has no row in :data:`PEAKS`."""


def lookup(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the benchmark's peaks "
            f"table {sorted(PEAKS)}"
        ) from None
