"""Published peaks of each chip, keyed by ``jax.Device.device_kind``.

"TPU v5 lite" is TPU v5e (Google Cloud documentation, "TPU v5e"): 197
TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect.  A device missing from the table is an error,
never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float        # bf16 FLOP/s per chip
    hbm_bytes: float    # HBM bytes/s per chip
    hbm_capacity: float  # HBM bytes per chip


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bytes=819e9, hbm_capacity=16e9),
}


def lookup(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r};"
                         f" known: {sorted(PEAKS)}") from None
