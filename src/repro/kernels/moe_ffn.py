"""Pallas TPU kernels: XShare masked expert FFN (dense combine) and the
sort-based grouped-GEMM expert FFN.

This is where the paper's memory-IO saving becomes *structural* on TPU:
the grid iterates over occupied expert work (a static budget, not all E
experts), and the weight BlockSpec index maps are functions of
scalar-prefetched expert-id vectors. An expert outside the selected /
routed set is therefore never DMA'd from HBM to VMEM at all — per-step
expert-weight traffic scales with the XShare-selected set, not with E,
the TPU-native analogue of the paper's "fewer experts loaded from GPU
memory".

Two kernels:

``moe_ffn``     — every expert runs over the whole (T, d) block and the
                  combine matrix masks; right for decode-sized T where
                  one x block fits VMEM and most tokens hit most active
                  experts. Grid: (max_active, d_ff tiles).

``grouped_ffn`` — the prefill-scale path. Tokens arrive pre-sorted into
                  expert-contiguous order, each expert's segment padded
                  to a multiple of ``block_t`` (models/dispatch.py
                  builds that layout with an argsort + bincount/cumsum).
                  The grid iterates over occupied row tiles via a
                  scalar-prefetched per-tile expert-id vector computed
                  from the segment offsets, so each token row is
                  touched once and each occupied expert's weights are
                  DMA'd once per f-tile — compute and weight traffic
                  are both capacity-free. Grid: (row tiles, d_ff tiles).

The FFN hidden axis is tiled so each step's working set (x tile + 3
weight tiles + accumulator) fits VMEM; tile sizes default to
MXU-aligned multiples of 128.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import resolve_interpret


def _kernel(ids_ref, valid_ref, x_ref, w1_ref, w3_ref, w2_ref, comb_ref,
            o_ref, acc_ref, *, num_f_tiles: int):
    slot = pl.program_id(0)
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid_ref[slot] > 0)
    def _compute():
        xb = x_ref[...].astype(jnp.float32)               # (T, d)
        h = xb @ w1_ref[0].astype(jnp.float32)            # (T, bf)
        g = xb @ w3_ref[0].astype(jnp.float32)
        h = jax.nn.silu(h) * g
        y = h @ w2_ref[0].astype(jnp.float32)             # (T, d)
        acc_ref[...] += comb_ref[0].astype(jnp.float32) * y  # (T, 1) gate

    @pl.when(fi == num_f_tiles - 1)
    def _emit():
        # accumulate this expert's contribution into the output
        @pl.when(slot == 0)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)
        o_ref[...] += acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("max_active", "block_f",
                                             "interpret"))
def moe_ffn(x: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
            w2: jnp.ndarray, combine: jnp.ndarray, active: jnp.ndarray, *,
            max_active: int, block_f: int = 512,
            interpret: Optional[bool] = None) -> jnp.ndarray:
    """XShare masked expert FFN. See ref.moe_ffn_ref for semantics.

    max_active: static upper bound on |selected set| (the XShare budget
    bound k0*T + m_l, capped at E). Weight HBM traffic scales with this,
    not with E.
    """
    interpret = resolve_interpret(interpret)
    T, d = x.shape
    E, _, f = w1.shape
    max_active = min(max_active, E)
    bf = min(block_f, f)
    assert f % bf == 0, (f, bf)
    nf = f // bf

    ids = jnp.nonzero(active, size=max_active, fill_value=0)[0]
    ids = ids.astype(jnp.int32)
    valid = (jnp.arange(max_active) < active.sum()).astype(jnp.int32)
    # one (T, 1) gate column per expert: as the trailing dims of an
    # (E, T, 1) array the block equals the full extent, which the TPU
    # lowering accepts; a (T, 1) block of the (T, E) matrix it refuses
    comb_cols = combine.T[:, :, None]

    grid = (max_active, nf)
    out = pl.pallas_call(
        functools.partial(_kernel, num_f_tiles=nf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((T, d), lambda s, fi, ids, valid: (0, 0)),
                pl.BlockSpec((1, d, bf),
                             lambda s, fi, ids, valid: (ids[s], 0, fi)),
                pl.BlockSpec((1, d, bf),
                             lambda s, fi, ids, valid: (ids[s], 0, fi)),
                pl.BlockSpec((1, bf, d),
                             lambda s, fi, ids, valid: (ids[s], fi, 0)),
                pl.BlockSpec((1, T, 1),
                             lambda s, fi, ids, valid: (ids[s], 0, 0)),
            ],
            out_specs=pl.BlockSpec((T, d), lambda s, fi, ids, valid: (0, 0)),
            scratch_shapes=[pltpu.VMEM((T, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(ids, valid, x, w1, w3, w2, comb_cols)
    return out


# -------------------------------------------------- grouped (sorted) ------

def _grouped_kernel(eid_ref, valid_ref, xs_ref, w1_ref, w3_ref, w2_ref,
                    o_ref, acc_ref, *, num_f_tiles: int):
    ti = pl.program_id(0)
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid_ref[ti] > 0)
    def _compute():
        xb = xs_ref[...].astype(jnp.float32)              # (bt, d)
        h = xb @ w1_ref[0].astype(jnp.float32)            # (bt, bf)
        g = xb @ w3_ref[0].astype(jnp.float32)
        h = jax.nn.silu(h) * g
        acc_ref[...] += h @ w2_ref[0].astype(jnp.float32)  # (bt, d)

    @pl.when(fi == num_f_tiles - 1)
    def _emit():
        # each row tile owns its output block; padding / out-of-range
        # tiles never accumulated, so they emit the zero-initialized acc
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f",
                                             "interpret"))
def grouped_ffn(xs: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                w2: jnp.ndarray, tile_eid: jnp.ndarray,
                tile_valid: jnp.ndarray, *, block_t: int,
                block_f: int = 512,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Grouped expert FFN over an expert-sorted, tile-padded row layout.

    xs: (P, d) token rows gathered into expert-contiguous order, each
    expert's segment zero-padded to a multiple of block_t (P itself a
    multiple of block_t). tile_eid: (P/block_t,) int32 — the expert
    owning each row tile (clamped into [0, E) for padding tiles);
    tile_valid: (P/block_t,) int32 — 0 for tiles past the last occupied
    segment (their rows emit zeros and their weight blocks resolve to
    tile_eid's clamped id, so unrouted experts cost no HBM traffic).

    Returns ys (P, d): ys[i] = FFN_{expert(i)}(xs[i]). Gate weighting
    and the scatter back to token order happen outside (the combine is
    a (T*k,)-sized scatter-add, not a (T, E, C) einsum).
    """
    interpret = resolve_interpret(interpret)
    P, d = xs.shape
    E, _, f = w1.shape
    assert P % block_t == 0, (P, block_t)
    nt = P // block_t
    assert tile_eid.shape == (nt,), (tile_eid.shape, nt)
    bf = min(block_f, f)
    assert f % bf == 0, (f, bf)
    nf = f // bf

    grid = (nt, nf)
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, num_f_tiles=nf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_t, d),
                             lambda t, fi, eid, valid: (t, 0)),
                pl.BlockSpec((1, d, bf),
                             lambda t, fi, eid, valid: (eid[t], 0, fi)),
                pl.BlockSpec((1, d, bf),
                             lambda t, fi, eid, valid: (eid[t], 0, fi)),
                pl.BlockSpec((1, bf, d),
                             lambda t, fi, eid, valid: (eid[t], fi, 0)),
            ],
            out_specs=pl.BlockSpec((block_t, d),
                                   lambda t, fi, eid, valid: (t, 0)),
            scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((P, d), xs.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(tile_eid.astype(jnp.int32), tile_valid.astype(jnp.int32),
      xs, w1, w3, w2)
    return out


def grouped_ffn_apply(xs: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                      w2: jnp.ndarray, plan, *,
                      use_kernel: Optional[bool] = None,
                      block_f: int = 512) -> jnp.ndarray:
    """The one resolution point for "Pallas grouped_ffn or tile-gather
    einsum?" over a DispatchPlan layout — shared by the single-device
    sorted pipeline (models/dispatch.sorted_expert_ffn) and the
    per-shard grouped GEMM inside the shard_map EP executor
    (ep/executor.py), so both paths pick the same backend the same way.

    use_kernel: None = auto (the Pallas kernel wherever it would
    compile, i.e. not interpret mode; the jnp tile-gather einsum
    elsewhere), True/False forces.
    """
    if use_kernel is None:
        use_kernel = not resolve_interpret(None)
    if use_kernel:
        return grouped_ffn(xs, w1, w3, w2, plan.tile_eid, plan.tile_valid,
                           block_t=plan.block_t,
                           block_f=min(block_f, w1.shape[2]))
    from repro.models.dispatch import grouped_ffn_jnp
    return grouped_ffn_jnp(xs, w1, w3, w2, plan)
