"""Activation-sharding constraints usable from model code without
threading mesh handles everywhere.

Model code calls ``constrain(x, "batch", None, "model", ...)``; under a
configured mesh context (launch/dryrun/train) this becomes
``with_sharding_constraint`` with "batch" resolved to the configured
data axes tuple; outside any context (CPU unit tests) it is a no-op.
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

_STATE = {"mesh": None, "batch_axes": (), "disabled": frozenset()}


@contextlib.contextmanager
def mesh_context(mesh, batch_axes: Tuple[str, ...],
                 disable: Tuple[str, ...] = ()):
    old = dict(_STATE)
    _STATE["mesh"] = mesh
    _STATE["batch_axes"] = tuple(batch_axes)
    _STATE["disabled"] = frozenset(disable)
    try:
        yield
    finally:
        _STATE.update(old)


def current_mesh():
    return _STATE["mesh"]


def batch_axes() -> Tuple[str, ...]:
    return _STATE["batch_axes"]


def model_axis_size() -> int:
    """Extent of the "model" mesh axis (1 outside any mesh context).

    The expert axis shards contiguously over "model", so this is also
    the number of expert-parallel shards: sorted dispatch rounds its
    tile count to a multiple of it and constrains the tile axis over
    "model" (expert-contiguous tiles => per-shard segments)."""
    mesh = _STATE["mesh"]
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)


def make_ep_mesh(num_shards: Optional[int] = None, axis: str = "model"):
    """A 1-D expert-parallel mesh over the first ``num_shards`` local
    devices (all of them by default). The axis name defaults to "model"
    — the axis the expert dimension shards over everywhere else in the
    repo — so EP composes with the existing partition rules."""
    n = len(jax.devices()) if num_shards is None else num_shards
    if n > len(jax.devices()):
        raise ValueError(
            f"make_ep_mesh({n}) but only {len(jax.devices())} devices "
            f"visible (set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count=N before importing jax to emulate)")
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]).reshape(n), (axis,))


def constrain(x, *axes: Optional[str], tag: Optional[str] = None):
    """axes entries: None, "model", or "batch" (mapped to the configured
    data-parallel axes tuple). Tagged constraints can be disabled per
    mesh_context (perf experiments, e.g. tag="seqpar")."""
    mesh = _STATE["mesh"]
    if mesh is None or (tag and tag in _STATE["disabled"]):
        return x
    resolved = []
    for a in axes:
        if a == "batch":
            ba = _STATE["batch_axes"]
            resolved.append(ba if ba else None)
        else:
            resolved.append(a)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))
