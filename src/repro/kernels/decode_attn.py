"""Pallas TPU kernel: flash-decode GQA — one query token per sequence
scored against a long KV cache, online softmax over sequence blocks.

Grid: (batch, seq blocks). The KV cache never materializes an (S,) score
tensor in HBM; each step streams one (block_s, Hkv, dh) tile of K and V
through VMEM and keeps the (H,) running max / normalizer / accumulator
in VMEM scratch. This is the decode-phase memory-bound hot loop the
paper's setting lives in: per step, bytes = KV-cache traffic, so the
roofline memory term tracks cache size directly.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import resolve_interpret

_NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_s: int, num_blocks: int, rep: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                     # (H, dh)
    k = k_ref[0].astype(jnp.float32)                     # (bs, Hkv, dh)
    v = v_ref[0].astype(jnp.float32)
    H, dh = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(Hkv, rep, dh)
    s = jnp.einsum("grd,sgd->grs", qg, k) / math.sqrt(dh)  # (Hkv,rep,bs)
    s = s.reshape(H, -1)                                  # (H, bs)
    cols = j * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    mask = cols < len_ref[b]
    maskf = mask.astype(jnp.float32)
    s = jnp.where(mask, s, _NEG)

    m_prev = m_scr[...]                                   # (H, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * maskf                        # (H, bs)
    alpha = jnp.exp(m_prev - m_new)                       # (H, 1)
    pv = jnp.einsum("grs,gsd->grd", p.reshape(Hkv, rep, -1),
                    v.transpose(1, 0, 2))                 # (Hkv,rep,dh)
    acc_scr[...] = acc_scr[...] * alpha + pv.reshape(H, dh)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(j == num_blocks - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, *, block_s: int = 512,
                     interpret=None) -> jnp.ndarray:
    """q: (B, H, dh); k/v: (B, S, Hkv, dh); lengths: (B,) valid lengths.

    Returns (B, H, dh). See ref.decode_attn_ref.
    """
    interpret = resolve_interpret(interpret)
    B, H, dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    bs = min(block_s, S)
    Sp = ((S + bs - 1) // bs) * bs
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    nb = Sp // bs

    out = pl.pallas_call(
        functools.partial(_kernel, block_s=bs, num_blocks=nb, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[
                pl.BlockSpec((1, H, dh), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, bs, Hkv, dh),
                             lambda b, j, lens: (b, j, 0, 0)),
                pl.BlockSpec((1, bs, Hkv, dh),
                             lambda b, j, lens: (b, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, dh), lambda b, j, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), q, k, v)
    return out
