"""Expert-parallel execution layer: histogram-driven placement with
hot-expert replication (placement.py) and the real shard_map sorted-
dispatch path with ragged all-to-all row exchange (executor.py).

``ep_context`` binds an EPExecutor for model code: ``expert_ffn``'s
``dispatch="ep"`` mode routes through the bound executor, and raises
when none is bound.
"""
from __future__ import annotations

import contextlib

from repro.ep.placement import (Placement, contiguous_placement,
                                placement_peak, plan_placement, rebalance)
from repro.ep.executor import (EP_TOL_EPS, EPExecutor, EPStats,
                               ep_mismatch, exchange_counts)

_STATE = {"executor": None}


@contextlib.contextmanager
def ep_context(executor: EPExecutor):
    """Bind an EPExecutor for ``expert_ffn(dispatch="ep")`` callers."""
    old = _STATE["executor"]
    _STATE["executor"] = executor
    try:
        yield executor
    finally:
        _STATE["executor"] = old


def current_executor():
    return _STATE["executor"]


__all__ = [
    "EP_TOL_EPS", "EPExecutor", "EPStats", "Placement",
    "contiguous_placement", "current_executor", "ep_context",
    "ep_mismatch", "exchange_counts", "placement_peak", "plan_placement",
    "rebalance",
]
