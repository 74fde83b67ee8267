"""Production mesh definitions (TPU v5e target).

Single pod slice: 256 chips as (16, 16) = ("data", "model").
Multi-pod:        2 pods x 256   = (2, 16, 16) = ("pod", "data", "model").

Functions, not module constants — importing this module never touches jax
device state (smoke tests must keep seeing 1 CPU device). When more devices
exist than a mesh needs (e.g. the 512-device dry-run process building the
single-pod 256 mesh), the first prod(shape) devices are used.

Every axis is ``Auto``: the model code places tensors with sharding
constraints and leaves the rest to the partitioner, so no operation needs
an explicit output sharding.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            f"(launch/dryrun.py does this)")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devs[:n])


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny mesh with the same axis names for fast iteration/tests."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)
