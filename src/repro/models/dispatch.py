"""Sort-based grouped expert dispatch — the capacity-free MoE hot path.

The GShard einsum dispatch in ``models/moe.py`` materializes
``(G, t, E, C)`` one-hot dispatch/combine tensors and runs every expert
at fixed capacity C, so both compute and memory scale with E even when
XShare has shrunk the routed set to a handful of experts. This module
replaces that with the sort/scatter pipeline used by modern MoE
inference stacks (MegaBlocks-style grouped GEMM):

  1. flatten the (T, k) token-expert assignments to N = T*k pairs and
     argsort them by expert id (stable, so within an expert tokens stay
     in batch order and an optional capacity clamp keeps the *first*
     tokens — GShard drop semantics);
  2. bincount + exclusive cumsum give per-expert segment offsets; each
     segment is padded to a multiple of ``block_t`` so every row tile
     belongs to exactly one expert;
  3. gather token rows into that expert-contiguous padded layout and
     run a grouped GEMM over the occupied tiles — either the Pallas
     ``kernels.moe_ffn.grouped_ffn`` kernel (compiled on TPU; weight
     blocks are DMA'd per occupied tile via scalar-prefetched tile
     expert ids) or a pure-jnp tile-gather einsum with identical
     layout semantics (the CPU / interpret-free fallback);
  4. scatter-combine the per-row FFN outputs back to token order with
     the gate weights — an (N,)-indexed scatter-add, not a (T, E, C)
     einsum.

Everything is shape-static under jit: the padded row buffer is sized
for the worst case (every occupied expert wastes block_t - 1 rows) and
unoccupied tail tiles are masked via ``tile_valid``.

Expert-parallel note: tiles are expert-contiguous and experts shard
contiguously over the mesh "model" axis, so constraining the *tile*
axis over "model" places each expert group's segments on its own
shard; per-shard load is the group's real segment sizes (see
``group_token_loads``), not E/G * C capacity padding.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.sharding import constrain, current_mesh, model_axis_size


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def default_block_t(num_pairs: int, num_experts: int) -> int:
    """Row-tile size: ~half the mean segment length, power of two,
    clamped to [8, 256] (MXU sublane-friendly without exploding the
    padded buffer when segments are ragged)."""
    target = max(8, num_pairs // (2 * num_experts))
    bt = 8
    while bt * 2 <= min(target, 256):
        bt *= 2
    return bt


class DispatchPlan(NamedTuple):
    """Static-shape sorted-dispatch layout for one (T, k, E) routing.

    All arrays are jnp; P (padded rows) and block_t are Python ints
    baked into the trace.
    """
    order: jnp.ndarray       # (N,) argsort of pairs by expert id
    s_tok: jnp.ndarray       # (N,) token index of each sorted pair
    s_w: jnp.ndarray         # (N,) gate weight (0 for dropped pairs)
    dest: jnp.ndarray        # (N,) padded-row index (P => dropped)
    counts: jnp.ndarray      # (E,) real per-expert segment sizes
    tile_eid: jnp.ndarray    # (P/block_t,) owning expert per row tile
    tile_valid: jnp.ndarray  # (P/block_t,) 1 = tile holds real rows
    block_t: int
    padded_rows: int         # P


def dispatch_plan(idx: jnp.ndarray, w: jnp.ndarray, num_experts: int, *,
                  block_t: Optional[int] = None,
                  capacity: Optional[int] = None,
                  max_active: Optional[int] = None,
                  pad_shards: Optional[int] = None) -> DispatchPlan:
    """Build the sorted grouped-dispatch layout.

    idx/w: (T, k) routing decisions; idx == -1 (masked continuous-
    batching slots) and w == 0 pairs are dropped — they consume no rows,
    no tiles, and no expert-weight traffic. capacity: optional per-
    expert clamp (tokens beyond it are dropped, first-in-batch kept —
    the EP load bound); None = capacity-free. max_active: static bound
    on the number of occupied experts (XShare budget) — shrinks the
    padded buffer and tile count, i.e. the thing weight traffic scales
    with. pad_shards: explicit tile-axis divisibility (EP shard count);
    None consults the ambient mesh context — the shard_map executor
    passes 1 because its per-shard plans must not inherit the outer
    mesh's padding.
    """
    T, k = idx.shape
    E = num_experts
    N = T * k
    bt = default_block_t(N, E) if block_t is None else block_t
    occ_bound = min(E, N) if max_active is None else min(max_active, E, N)
    P = _round_up(N + occ_bound * (bt - 1), bt)
    if pad_shards is None:
        pad_shards = model_axis_size() if current_mesh() is not None else 1
    if pad_shards > 1:
        # keep the tile axis divisible by the model axis so the sorted
        # layout can shard over it (EP)
        P = _round_up(P, bt * pad_shards)
    num_tiles = P // bt

    flat_e = idx.reshape(N).astype(jnp.int32)
    flat_w = w.reshape(N).astype(jnp.float32)
    tok = jnp.arange(N, dtype=jnp.int32) // k
    live = (flat_e >= 0) & (flat_e < E) & (flat_w != 0.0)
    key = jnp.where(live, flat_e, E)          # sentinel E sorts last

    order = jnp.argsort(key)                  # stable: batch order kept
    s_e = key[order]
    s_w = jnp.where(live[order], flat_w[order], 0.0)
    s_tok = tok[order]

    raw_counts = jnp.zeros((E,), jnp.int32).at[key].add(1, mode="drop")
    counts = raw_counts if capacity is None else \
        jnp.minimum(raw_counts, capacity)
    # raw segment starts give each sorted row its within-expert rank;
    # the clamp drops the rank >= capacity tail, so kept rows keep
    # contiguous ranks 0..counts-1 and dest needs no re-compaction
    raw_start = jnp.cumsum(raw_counts) - raw_counts
    e_clip = jnp.clip(s_e, 0, E - 1)
    rank = jnp.arange(N, dtype=jnp.int32) - raw_start[e_clip]
    kept = (s_e < E) & (rank < counts[e_clip])
    s_w = jnp.where(kept, s_w, 0.0)

    pad_counts = ((counts + bt - 1) // bt) * bt
    pad_start = jnp.cumsum(pad_counts) - pad_counts
    dest = jnp.where(kept, pad_start[e_clip] + rank, P)

    pad_end = jnp.cumsum(pad_counts)
    tile_start = jnp.arange(num_tiles, dtype=jnp.int32) * bt
    owner = jnp.searchsorted(pad_end, tile_start, side="right")
    tile_valid = (owner < E).astype(jnp.int32)
    # tail tiles point at the FIRST occupied expert (owner[0]), not a
    # clamped E-1: the kernel's weight index maps would otherwise DMA an
    # unrouted last expert's blocks for every padding tile
    fallback = jnp.where(owner[0] < E, owner[0], 0)
    tile_eid = jnp.where(owner < E, owner, fallback).astype(jnp.int32)
    return DispatchPlan(order=order, s_tok=s_tok, s_w=s_w, dest=dest,
                        counts=counts, tile_eid=tile_eid,
                        tile_valid=tile_valid, block_t=bt, padded_rows=P)


def gather_tokens(x: jnp.ndarray, plan: DispatchPlan) -> jnp.ndarray:
    """x: (T, d) -> (P, d) expert-contiguous padded rows (zeros in the
    padding — FFN(0) = 0, so padding never pollutes the combine)."""
    xs = jnp.zeros((plan.padded_rows, x.shape[1]), x.dtype)
    return xs.at[plan.dest].set(x[plan.s_tok], mode="drop")


def grouped_ffn_jnp(xs: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                    w2: jnp.ndarray, plan: DispatchPlan) -> jnp.ndarray:
    """Pure-jnp grouped GEMM over the padded tile layout — identical
    semantics to kernels.moe_ffn.grouped_ffn, XLA-lowered (the fast
    path off-TPU, where the Pallas interpreter would run Python).

    Weight tiles are gathered per row tile (tile_eid), so compute and
    gathered-weight memory scale with occupied tiles (~N/block_t +
    occupied experts), never with E * capacity.
    """
    P, d = xs.shape
    bt = plan.block_t
    nt = P // bt
    xs3 = xs.reshape(nt, bt, d)
    xs3 = constrain(xs3, "model", None, None, tag="ep_sorted")
    w1g = jnp.asarray(w1, jnp.float32)[plan.tile_eid]       # (nt, d, f)
    w3g = jnp.asarray(w3, jnp.float32)[plan.tile_eid]
    w2g = jnp.asarray(w2, jnp.float32)[plan.tile_eid]       # (nt, f, d)
    xf = jnp.asarray(xs3, jnp.float32)
    h = jnp.einsum("tbd,tdf->tbf", xf, w1g)
    h = jax.nn.silu(h) * jnp.einsum("tbd,tdf->tbf", xf, w3g)
    ys = jnp.einsum("tbf,tfd->tbd", h, w2g)
    ys = constrain(ys, "model", None, None, tag="ep_sorted")
    return ys.reshape(P, d).astype(xs.dtype)


def combine_scatter(ys: jnp.ndarray, plan: DispatchPlan,
                    num_tokens: int, out_dtype) -> jnp.ndarray:
    """Scatter-combine per-row expert outputs back to token order:
    y[t] = sum over t's kept pairs of gate_w * FFN_e(x[t])."""
    P = plan.padded_rows
    rows = ys[jnp.minimum(plan.dest, P - 1)]          # (N, d)
    contrib = plan.s_w[:, None] * jnp.asarray(rows, jnp.float32)
    y = jnp.zeros((num_tokens, ys.shape[1]), jnp.float32)
    y = y.at[plan.s_tok].add(contrib)
    return y.astype(out_dtype)


def group_token_loads(counts: jnp.ndarray, num_groups: int) -> jnp.ndarray:
    """Real per-device-group load: token-assignment rows landing on each
    contiguous expert group (the EP shard map), from actual segment
    sizes — what a device computes under sorted dispatch, as opposed to
    the E/G * C rows the capacity-padded einsum path always pays.

    Non-divisible E: groups are ceil(E/G) experts wide with the last
    group(s) smaller (zero-padded), matching ``ep_select`` and
    ``contiguous_placement`` — the old code silently collapsed to one
    group, reporting the whole batch as one shard's load."""
    E = counts.shape[0]
    per = -(-E // num_groups)
    padded = jnp.pad(counts, (0, num_groups * per - E))
    return padded.reshape(num_groups, per).sum(-1)


def sorted_expert_ffn(x: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                      w2: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray, *,
                      block_t: Optional[int] = None,
                      capacity: Optional[int] = None,
                      max_active: Optional[int] = None,
                      use_kernel: Optional[bool] = None,
                      block_f: int = 512) -> jnp.ndarray:
    """Full sorted pipeline: plan -> gather -> grouped GEMM -> scatter.

    use_kernel: None = auto (Pallas grouped_ffn when it would compile,
    i.e. on TPU; jnp tile-gather einsum elsewhere), True/False forces.
    """
    from repro.kernels.moe_ffn import grouped_ffn_apply
    T = x.shape[0]
    E = w1.shape[0]
    with jax.named_scope("dispatch"):
        plan = dispatch_plan(idx, w, E, block_t=block_t, capacity=capacity,
                             max_active=max_active)
        xs = gather_tokens(x, plan)
    with jax.named_scope("experts"):
        ys = grouped_ffn_apply(xs, w1, w3, w2, plan, use_kernel=use_kernel,
                               block_f=block_f)
    with jax.named_scope("combine"):
        return combine_scatter(ys, plan, T, x.dtype)
