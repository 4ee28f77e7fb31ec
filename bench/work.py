"""Operations and bytes of the work the benchmark asks for, computed
from shapes alone (the sizes of ``arch/<architecture>.dims``).

A matmul of (m, k) by (k, n) is 2·m·k·n operations. Weights and rows are
bfloat16, 2 bytes an element. The formulas here are those of the
decoder block with grouped-query attention and top-k routed experts in
every layer. Where the sizes name their architecture
(``z["architecture"]``, as a run's do) and ``arch/<architecture>.py``
defines a function of the same name, that function counts instead.
"""
from __future__ import annotations

import functools
import importlib

BYTES = 2


def architecture_may_count(fn):
    """`fn`, or the function of its name in the module of the sizes'
    architecture, where that module defines one."""
    @functools.wraps(fn)
    def count(z, *args):
        arch = z.get("architecture")
        own = arch and getattr(importlib.import_module("arch." + arch),
                               fn.__name__, None)
        return (own or fn)(z, *args)
    return count


@architecture_may_count
def expert_params(z) -> int:
    """Weights of one expert: w1, w3 (d x f) and w2 (f x d)."""
    return 3 * z["d"] * z["f"]


@architecture_may_count
def grouped_ffn_flops(z, pairs: int) -> int:
    """One grouped_ffn call over `pairs` (token, expert) rows."""
    return 2 * pairs * expert_params(z)


@architecture_may_count
def grouped_ffn_bytes(z, pairs: int, experts: int) -> int:
    """The least the call must move: each occupied expert's weights
    once, each row in and out once."""
    return BYTES * (experts * expert_params(z) + 2 * pairs * z["d"])


@architecture_may_count
def token_flops(z, context: int) -> int:
    """Model operations for one token that sees `context` positions
    (itself included): per layer the q/k/v/o projections, the router,
    the top-k experts and the attention scores and sums; then the head
    over the real vocabulary."""
    d, H, Hkv, dh = z["d"], z["H"], z["Hkv"], z["dh"]
    proj = 2 * d * (2 * H * dh + 2 * Hkv * dh)
    router = 2 * d * z["E"]
    experts = z["k"] * 2 * expert_params(z)
    attn = 2 * 2 * H * dh * context
    return z["L"] * (proj + router + experts + attn) + 2 * d * z["V"]


@architecture_may_count
def prefill_flops(z, length: int) -> int:
    """One prompt of `length` tokens, causal: token p sees p + 1. Only
    the last position goes through the head."""
    per_layer_attn = 2 * 2 * z["H"] * z["dh"] * length * (length + 1) // 2
    body = token_flops(z, 0) - 2 * z["d"] * z["V"]
    return length * body + z["L"] * per_layer_attn + 2 * z["d"] * z["V"]
