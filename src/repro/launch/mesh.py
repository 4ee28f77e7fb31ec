"""Production mesh construction.

A FUNCTION, not a module constant: importing this module never touches
jax device state. The dry-run entry point sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; smoke tests and benchmarks see the real (1-device) platform.

Target hardware (TPU v5e pod): 16x16 = 256 chips per pod; multi-pod is
2 pods = 512 chips with the "pod" axis crossing DCI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Small mesh over however many devices exist (tests/examples)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
