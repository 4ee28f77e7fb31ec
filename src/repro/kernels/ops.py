"""Public jit'd wrappers around the Pallas kernels, plus byte-traffic
models used by the roofline analysis and OTPS modeling.

``interpret`` defaults to None everywhere = auto-detect (compiled on
TPU, Python interpreter elsewhere; see kernels/compat.resolve_interpret).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels.decode_attn import decode_attention
from repro.kernels.moe_ffn import grouped_ffn, moe_ffn
from repro.kernels.ssd_scan import ssd_scan

__all__ = ["xshare_moe_ffn", "xshare_grouped_ffn", "flash_decode",
           "ssd_chunk_scan", "moe_step_bytes", "dispatch_einsum_bytes",
           "dispatch_sorted_bytes"]


def xshare_moe_ffn(x, w1, w3, w2, combine, active, *,
                   max_active: Optional[int] = None, block_f: int = 512,
                   interpret: Optional[bool] = None):
    """Masked expert FFN; weight HBM traffic ~ max_active, not E."""
    E = w1.shape[0]
    ma = E if max_active is None else min(max_active, E)
    bf = block_f
    while w1.shape[2] % bf:
        bf //= 2
    return moe_ffn(x, w1, w3, w2, combine, active, max_active=ma,
                   block_f=bf, interpret=interpret)


def xshare_grouped_ffn(xs, w1, w3, w2, tile_eid, tile_valid, *,
                       block_t: int, block_f: int = 512,
                       interpret: Optional[bool] = None):
    """Sort-based grouped expert FFN over a tile-padded sorted layout
    (models/dispatch.py builds it); weight HBM traffic ~ occupied
    experts, compute ~ routed rows — both capacity-free."""
    bf = min(block_f, w1.shape[2])
    while w1.shape[2] % bf:
        bf //= 2
    return grouped_ffn(xs, w1, w3, w2, tile_eid, tile_valid,
                       block_t=block_t, block_f=bf, interpret=interpret)


def flash_decode(q, k, v, lengths, *, block_s: int = 512,
                 interpret: Optional[bool] = None):
    return decode_attention(q, k, v, lengths, block_s=block_s,
                            interpret=interpret)


def ssd_chunk_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, block_h: int = 8,
                   interpret: Optional[bool] = None):
    bh = block_h
    while x.shape[2] % bh:
        bh //= 2
    return ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, block_h=bh,
                    interpret=interpret)


def moe_step_bytes(num_active: float, d_model: int, d_ff: int,
                   dtype_bytes: int = 2, *, tokens: int = 0,
                   top_k: int = 0) -> float:
    """HBM bytes per MoE layer per decode step under XShare.

    Expert weights dominate in the decode regime (the paper's premise):
    3 * d * f per expert, fetched once per step for each *activated*
    expert; activations add 2*T*d + routed intermediate traffic.
    """
    w = num_active * 3 * d_model * d_ff * dtype_bytes
    act = tokens * d_model * dtype_bytes * (2 + 2 * top_k)
    return w + act


def dispatch_einsum_bytes(tokens: int, num_experts: int, capacity: int,
                          d_model: int, dtype_bytes: int = 4,
                          groups: int = 1) -> float:
    """Peak dispatch-intermediate footprint of the GShard einsum path:
    the (G, t, E, C) dispatch + combine one-hots and the (G, E, C, d)
    gathered/expert-output activations — all scale with E * C whether
    or not an expert is routed."""
    t = tokens // groups
    onehots = 2 * groups * t * num_experts * capacity * dtype_bytes
    expert_act = 2 * groups * num_experts * capacity * d_model * dtype_bytes
    return onehots + expert_act


def dispatch_sorted_bytes(tokens: int, top_k: int, num_experts: int,
                          d_model: int, dtype_bytes: int = 4,
                          block_t: int = 128,
                          max_active: Optional[int] = None) -> float:
    """Peak dispatch-intermediate footprint of the sorted grouped path:
    the (P, d) gathered rows + (P, d) expert outputs where
    P = T*k (+ tile padding per occupied expert), plus the (N,)-sized
    sort/offset vectors. Scales with routed pairs, not E * C.

    Weight traffic is intentionally excluded on both sides: the Pallas
    kernel streams weight tiles through VMEM (never materialized), and
    the einsum path reads each expert's weights once too. The CPU
    tile-gather fallback does materialize per-tile weight copies — the
    benchmark reports those separately (sorted_jnp_weight_gather_bytes)."""
    n = tokens * top_k
    occ = min(num_experts, n) if max_active is None else max_active
    p = n + occ * (block_t - 1)
    rows = 2 * p * d_model * dtype_bytes
    vecs = 5 * n * 4
    return rows + vecs
