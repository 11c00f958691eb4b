"""Production mesh construction.

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Target hardware (TPU v5e): 197 TFLOP/s bf16/chip, 819 GB/s HBM, ~50 GB/s/link
ICI. Single pod = 16x16 = 256 chips (data x model); multi-pod = 2 pods = 512
chips with a leading "pod" axis (DCN-ish slower axis — keep only DP traffic
on it).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import jax

from repro import compat

# Roofline hardware constants (TPU v5e-class, per assignment)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def make_production_mesh(*, multi_pod: bool = False,
                         devices: Optional[list] = None) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — run "
            f"under launch/dryrun.py (sets xla_force_host_platform_device_count)")
    return compat.make_mesh(shape, axes, devices=devices[:n])


def make_local_mesh(shape: Tuple[int, ...] = (1, 1),
                    axes: Tuple[str, ...] = ("data", "model"), *,
                    devices: Optional[Sequence[Any]] = None
                    ) -> jax.sharding.Mesh:
    """Mesh over the first ``prod(shape)`` of ``devices`` (default: the
    devices present) — the serving launchers' mesh."""
    n = math.prod(shape)
    devices = list(jax.devices() if devices is None else devices)
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return compat.make_mesh(shape, axes, devices=devices[:n])
