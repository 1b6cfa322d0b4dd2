"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the pod
axis joins data-parallelism (batch and FSDP shard over ('pod','data')),
so cross-pod traffic is gradient reduction only — the right placement for
the slow inter-pod links.

Functions, not module-level constants: importing this module must not
touch jax device state (smoke tests see 1 CPU device; only dryrun.py sets
XLA_FLAGS to fake 512 hosts).
"""
from __future__ import annotations

from typing import NamedTuple

import jax


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


class Peaks(NamedTuple):
    """Published per-chip peak rates."""
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # interconnect bytes/s per link
    source: str


#: Peak rates keyed by ``jax.Device.device_kind``.
PEAKS = {
    # 197 TFLOP/s bf16; 16 GB HBM at 819 GB/s; 1,600 Gbit/s of ICI over
    # four links = 50 GB/s per link
    "TPU v5 lite": Peaks(197e12, 819e9, 50e9,
                         "Google Cloud documentation, 'TPU v5e'"),
}


def peaks(device_kind: str) -> Peaks:
    """Peak rates of one chip of this kind; an unknown kind raises
    rather than borrowing another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak rates for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
