"""Pallas TPU kernel: Mamba2 chunked SSD scan.

Grid: (batch, head tiles, seq chunks) — the chunk axis is sequential
("arbitrary") and the running inter-chunk SSM state lives in VMEM
scratch, so the HBM traffic per chunk is just the chunk's activations:
the TPU adaptation of Mamba2's fused CUDA scan (intra-chunk work is
matmul-shaped for the MXU; the recurrence only crosses chunk
boundaries). Emits both the per-position outputs and the final state
(decode handoff).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import resolve_interpret


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr, *,
            num_chunks: int, chunk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)        # (l, bh, hd)
    dt = dt_ref[0].astype(jnp.float32)      # (l, bh)
    A = a_ref[...].astype(jnp.float32)      # (bh,)
    Bm = b_ref[0].astype(jnp.float32)       # (l, bh, ds)
    Cm = c_ref[0].astype(jnp.float32)       # (l, bh, ds)

    dA = dt * A[None, :]                    # (l, bh) <= 0
    cum = jnp.cumsum(dA, axis=0)            # (l, bh)

    # intra-chunk
    seg = cum[:, None, :] - cum[None, :, :]                 # (i, j, bh)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(tri[:, :, None], jnp.exp(seg), 0.0)
    scores = jnp.einsum("ihs,jhs->ijh", Cm, Bm)
    M = scores * decay * dt[None, :, :]                     # fold dt_j
    y = jnp.einsum("ijh,jhp->ihp", M, x)

    # inter-chunk contribution from the carried state
    y += jnp.einsum("ihs,hps,ih->ihp", Cm, state_scr[...], jnp.exp(cum))

    # state update
    decay_states = jnp.exp(cum[-1:, :] - cum)               # (l, bh)
    upd = jnp.einsum("lhs,lh,lhp->hps", Bm, decay_states * dt, x)
    state_scr[...] = state_scr[...] * jnp.exp(cum[-1])[:, None, None] + upd

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(j == num_chunks - 1)
    def _emit_state():
        st_ref[0] = state_scr[...].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "block_h",
                                             "interpret"))
def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, *, chunk: int = 128,
             block_h: int = 8, interpret=None):
    """x: (B,S,nh,hd); dt: (B,S,nh); A: (nh,); Bm/Cm: (B,S,nh,ds)
    (heads pre-broadcast). Returns (y (B,S,nh,hd), state (B,nh,hd,ds)).
    S must pad to a chunk multiple (dt padding 0 => exp(0)=1 decay,
    zero input: harmless)."""
    interpret = resolve_interpret(interpret)
    B, S, nh, hd = x.shape
    ds = Bm.shape[-1]
    l = min(chunk, S)
    Sp = ((S + l - 1) // l) * l
    if Sp != S:
        pad = ((0, 0), (0, Sp - S), (0, 0))
        x = jnp.pad(x, pad + ((0, 0),))
        dt = jnp.pad(dt, pad)
        Bm = jnp.pad(Bm, pad + ((0, 0),))
        Cm = jnp.pad(Cm, pad + ((0, 0),))
    nc = Sp // l
    bh = min(block_h, nh)
    assert nh % bh == 0
    nh_t = nh // bh

    y, st = pl.pallas_call(
        functools.partial(_kernel, num_chunks=nc, chunk=l),
        grid=(B, nh_t, nc),
        in_specs=[
            pl.BlockSpec((1, l, bh, hd), lambda b, h, j: (b, j, h, 0)),
            pl.BlockSpec((1, l, bh), lambda b, h, j: (b, j, h)),
            pl.BlockSpec((bh,), lambda b, h, j: (h,)),
            pl.BlockSpec((1, l, bh, ds), lambda b, h, j: (b, j, h, 0)),
            pl.BlockSpec((1, l, bh, ds), lambda b, h, j: (b, j, h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, l, bh, hd), lambda b, h, j: (b, j, h, 0)),
            pl.BlockSpec((1, bh, hd, ds), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, nh, hd), x.dtype),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, hd, ds), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, dt, A, Bm, Cm)
    return y[:, :S], st
