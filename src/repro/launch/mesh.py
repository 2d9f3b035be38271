"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).

Mesh layout (TPU v5e pods):
  single pod : (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

DP runs over ("pod","data"); TP/EP/SP over "model"; FSDP param sharding over
"data".  The "pod" axis only ever carries pure data parallelism + gradient
all-reduce, so cross-pod (DCI) traffic is one gradient reduction per step —
the layout that scales past 1000 nodes.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    n = math.prod(shape)
    devices = jax.devices()[:n]              # dry-run exposes 512 host devices
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} "
            "(the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import)")
    return make_mesh(shape, axes, devices=devices)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """Arbitrary mesh with GSPMD-auto axis types (tests use small meshes)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh():
    """Data-parallel mesh over every local device (trainer, batch engine)."""
    n = jax.device_count()
    return make_mesh((n, 1), ("data", "model"))


def make_device_mesh(device=None):
    """One-device ("data", "model") mesh: where one serving engine places
    its params and KV pool.  Defaults to the first device, so a one-chip
    path uses exactly one device even on a host with more."""
    device = device if device is not None else jax.devices()[0]
    return make_mesh((1, 1), ("data", "model"), devices=[device])
