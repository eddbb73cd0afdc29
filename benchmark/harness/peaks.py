"""Published peaks of the chips the benchmark divides by, keyed by
``jax.devices()[0].device_kind``. A chip that is not here is an error,
never a default. (Copied from ``apex_tpu/utils/chip_peaks.py``, so that
the yardstick cannot move with the program.)"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float        # dense bf16 FLOP/s per chip
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        197e12, 819e9, 16 * 1024 ** 3,
        "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, 16 GB "
        "HBM2e at 819 GB/s per chip"),
}


class UnknownChip(KeyError):
    """No published peaks for this ``device_kind`` (the CPU of a
    rehearsal, or a chip nobody has added with its source)."""


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise UnknownChip(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}. Add a row with its source.")
    return PEAKS[device_kind]
