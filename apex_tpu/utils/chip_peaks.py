"""Published peak rates of the chips this repo computes utilization for.

ONE table, keyed by ``jax.devices()[0].device_kind``, each row with its
source. A chip that is not in it is an error, never a default: a
utilization divided by the wrong peak is a wrong number that looks
right.
"""

from __future__ import annotations

from typing import NamedTuple


class ChipPeaks(NamedTuple):
    bf16_flops: float      # peak dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        197e12, 819e9, 16 * 1024 ** 3,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 16 GB "
        "HBM2e at 819 GB/s per chip"),
    "TPU v4": ChipPeaks(
        275e12, 1228e9, 32 * 1024 ** 3,
        "Google Cloud documentation, 'TPU v4': 275 TFLOP/s bf16, 32 GiB "
        "HBM2 at 1228 GB/s per chip"),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks recorded for device_kind "
            f"{device_kind!r}; known: {sorted(PEAKS)}. Add a row with its "
            f"source to apex_tpu/utils/chip_peaks.py") from None
