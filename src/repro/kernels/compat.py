"""Interpret-vs-compile resolution for the Pallas kernels.

Every Pallas entry point defaults ``interpret=None`` and resolves it
here: compiled on a TPU backend, the Pallas interpreter on any other
backend (the CPU test runs). An explicit argument always wins. Nothing
else can switch a kernel to interpret mode, so on the chip the kernels
always compile.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Explicit argument > backend auto-detect."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
