"""Published per-chip peak rates, keyed by ``jax.Device.device_kind``.

A kind that is not in the table is an error, never a default."""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float      #: dense bf16 FLOP/s
    hbm_bw: float     #: HBM bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9,
                         "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                         "bf16, 16 GB HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak rates for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
