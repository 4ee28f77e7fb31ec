"""Real expert-parallel execution of sorted dispatch under shard_map.

This replaces the tile-axis ``with_sharding_constraint`` approximation:
token rows are *actually exchanged* between shards. Per shard, per MoE
layer:

  1. route pairs to shards — each (token, expert) pair's destination is
     ``placement.hosts[e, token % nhosts[e]]``: the shard hosting the
     expert, with a replicated expert's rows split deterministically
     across its replicas (token-id modulus, so routing is reproducible
     and independent of shard count);
  2. per-shard argsort by destination + segment offsets (the same
     bincount/cumsum machinery as ``dispatch_plan``);
  3. ragged all-to-all — the (S,) send-count vector is exchanged first
     (one int per peer), then the payload, packed into per-peer
     segments padded to ``max_rows`` — the per-round maximum, NOT the
     GShard capacity E/G*C. Only occupied rows carry data; padding is
     zeros and expert-id -1;
  4. grouped GEMM over the received rows with the shard's *local*
     expert weights — the existing sorted pipeline verbatim
     (``dispatch_plan`` + ``gather_tokens`` + Pallas ``grouped_ffn`` on
     TPU / tile-gather einsum elsewhere + ``combine_scatter``), built
     over local expert slots so per-shard weight memory is
     ``placement.expert_cap`` experts, not E;
  5. reverse all-to-all ships each row's FFN output back to its source
     shard, which scatter-combines with the gate weights in
     expert-sorted pair order — the *same summation order* as the
     single-device sorted reference. The two agree to within
     ``EP_TOL_EPS`` (see ``ep_mismatch``), not bit for bit: the
     shard_map program compiles on its own, and XLA may fuse and
     round it differently.

``max_rows`` sizing: the worst case (every local pair to one peer) is
always exact; ``max_rows="auto"`` runs the counts-only exchange first
and buckets the observed per-peer maximum to a power of two, so the
payload is padded to the per-round max while recompiles stay bounded
(one compile per bucket). A count above ``max_rows`` clamps with
capacity semantics (first tokens kept, surplus pairs dropped with zero
gate weight).
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.ep.placement import (Placement, contiguous_placement,
                                placement_peak, plan_placement, rebalance)
from repro.models.dispatch import (combine_scatter, default_block_t,
                                   dispatch_plan, gather_tokens)

# EP output vs the single-device sorted path, per element, in machine
# epsilons of the output dtype (see ep_mismatch). The two paths round
# each expert row and the k-term combine in a different order, so an
# element may differ by a few roundings of its own size or of its
# token's largest output. Readings of sound runs: 2.94 (CPU, f32,
# top-8), at most 0.24 (CPU, bf16, Granite widths), 0 (TPU v5e, bf16).
# One dropped (token, expert) row or one wrong expert at Granite widths
# reads 23 or more (CPU, bf16); PERF.md keeps the readings.
EP_TOL_EPS = 4.0


def ep_mismatch(y, ref) -> float:
    """max |y - ref| / (eps(ref.dtype) * (|ref| + m)), m the largest
    |ref| in the element's row (its token): at most EP_TOL_EPS where the
    EP path agrees with the single-device reference. Scaling by the row,
    not the whole output, keeps a token with small outputs from hiding
    its error under another token's large ones."""
    r = np.abs(np.asarray(ref, np.float64))
    diff = np.abs(np.asarray(y, np.float64) - np.asarray(ref, np.float64))
    scale = float(jnp.finfo(ref.dtype).eps) * (
        r + r.max(axis=-1, keepdims=True))
    return float((diff / np.maximum(scale, 1e-300)).max())


class EPStats(NamedTuple):
    """Measured per-layer EP execution profile (host numpy).

    computed_rows[s] — real token-assignment rows shard s ran through
    its grouped GEMM (segment sizes, no tile padding). The max over
    shards is the bottleneck-device metric the paper's "peak GPU load"
    claim is about.
    tile_rows[s]     — rows shard s's grouped GEMM actually executed:
                       occupied tiles * block_t, i.e. segments rounded
                       up to the tile grid. At decode sizes (segments
                       of a few rows) this is dominated by the number
                       of *active experts* on the shard — the quantity
                       Algorithm 6 bounds — so it is the measured
                       per-device cost the EP scoreboard compares.
    sent_rows[s]     — rows shard s shipped to *other* shards.
    a2a_bytes[s]     — bytes shard s put on the interconnect: payload
                       forward + reverse, expert ids forward, and the
                       count vectors both ways.
    count_matrix     — (S, S) rows, [src, dst] routed rows.
    max_rows         — the per-peer payload padding this round used.
    """
    computed_rows: np.ndarray
    tile_rows: np.ndarray
    sent_rows: np.ndarray
    a2a_bytes: np.ndarray
    count_matrix: np.ndarray
    max_rows: int

    @property
    def peak_rows(self) -> int:
        return int(self.computed_rows.max())

    @property
    def peak_tile_rows(self) -> int:
        return int(self.tile_rows.max())

    @property
    def total_a2a_bytes(self) -> int:
        return int(self.a2a_bytes.sum())


def _route_pairs(idx, w, hosts, nhosts, tok0, num_experts, num_shards):
    """Flatten (T_loc, k) routing to pairs and pick each pair's
    destination shard (sentinel S for dead pairs)."""
    T_loc, k = idx.shape
    N = T_loc * k
    e = idx.reshape(N).astype(jnp.int32)
    wf = w.reshape(N).astype(jnp.float32)
    tokl = jnp.arange(N, dtype=jnp.int32) // k
    live = (e >= 0) & (e < num_experts) & (wf != 0.0)
    ec = jnp.clip(e, 0, num_experts - 1)
    gtok = tok0 + tokl
    nrep = jnp.maximum(nhosts[ec], 1)
    dest = jnp.where(live, hosts[ec, gtok % nrep], num_shards)
    return ec, wf, tokl, live, dest


def _build_counts_fn(mesh, axis: str, num_experts: int, num_shards: int):
    S = num_shards

    def body(idx, w, hosts, nhosts):
        rix = jax.lax.axis_index(axis)
        T_loc = idx.shape[0]
        _, _, _, live, dest = _route_pairs(
            idx, w, hosts, nhosts, rix * T_loc, num_experts, S)
        counts = jnp.zeros((S,), jnp.int32).at[dest].add(
            live.astype(jnp.int32), mode="drop")
        return counts[None]

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(axis), P(axis), P(), P()),
                                 out_specs=P(axis)))


def exchange_counts(idx, w, placement: Placement, *, mesh,
                    axis: str = "model") -> np.ndarray:
    """The counts phase alone: (S, S) matrix of rows each shard would
    send each peer this round. Drives ``max_rows="auto"`` payload
    sizing and placement-quality probes without moving any rows."""
    S = placement.num_shards
    T = idx.shape[0]
    pad = (-T) % S
    if pad:
        idx = jnp.concatenate(
            [idx, jnp.full((pad, idx.shape[1]), -1, idx.dtype)])
        w = jnp.concatenate([w, jnp.zeros((pad, w.shape[1]), w.dtype)])
    fn = _build_counts_fn(mesh, axis, placement.num_experts, S)
    out = fn(idx, w, jnp.asarray(placement.hosts),
             jnp.asarray(placement.nhosts))
    return np.asarray(out)


def _build_ep_fn(mesh, axis: str, *, num_shards: int, num_experts: int,
                 expert_cap: int, max_rows: int, block_t: int,
                 block_f: int, use_kernel: bool):
    """The jitted shard_map EP layer for one static configuration.

    Traced arguments: x (T, d), idx/w (T, k), the FULL expert weights
    (E, d, f)x3 (gathered into per-shard (S, cap, d, f) slices inside
    the trace from ``local_eids``, so a placement change is a new
    gather, not a new compile), and the placement lookup tables.
    """
    S, E, cap, M, bt = num_shards, num_experts, expert_cap, max_rows, \
        block_t

    def body(x, idx, w, hosts, nhosts, lslot, w1s, w3s, w2s):
        rix = jax.lax.axis_index(axis)
        T_loc, d = x.shape
        k = idx.shape[1]
        N = T_loc * k
        ec, wf, tokl, live, dest = _route_pairs(
            idx, w, hosts, nhosts, rix * T_loc, E, S)
        # --- per-shard sort by destination + segment offsets ----------
        order = jnp.argsort(dest)                 # stable: token order
        s_dest = dest[order]
        s_e = ec[order]
        s_live = live[order]
        send_counts = jnp.zeros((S,), jnp.int32).at[dest].add(
            live.astype(jnp.int32), mode="drop")
        start = jnp.cumsum(send_counts) - send_counts
        dclip = jnp.clip(s_dest, 0, S - 1)
        rank = jnp.arange(N, dtype=jnp.int32) - start[dclip]
        kept = s_live & (rank < M)                # M-overflow: capacity
        pos = jnp.where(kept, dclip * M + rank, S * M)  # drop semantics
        xbuf = jnp.zeros((S * M, d), x.dtype).at[pos].set(
            x[tokl[order]], mode="drop")
        ebuf = jnp.full((S * M,), -1, jnp.int32).at[pos].set(
            s_e, mode="drop")
        # --- ragged all-to-all: counts first, then padded payload -----
        recv_counts = jax.lax.all_to_all(send_counts, axis, 0, 0,
                                         tiled=True)
        recv_x = jax.lax.all_to_all(xbuf.reshape(S, M, d), axis, 0, 0,
                                    tiled=True).reshape(S * M, d)
        recv_e = jax.lax.all_to_all(ebuf.reshape(S, M), axis, 0, 0,
                                    tiled=True).reshape(S * M)
        # --- grouped GEMM over received rows, local expert slots ------
        lsl = jnp.where(recv_e >= 0,
                        lslot[0, jnp.clip(recv_e, 0, E - 1)], -1)
        plan = dispatch_plan(lsl[:, None],
                             (lsl >= 0).astype(jnp.float32)[:, None],
                             cap, block_t=bt, pad_shards=1)
        xs = gather_tokens(recv_x, plan)
        from repro.kernels.moe_ffn import grouped_ffn_apply
        ys = grouped_ffn_apply(xs, w1s[0], w3s[0], w2s[0], plan,
                               use_kernel=use_kernel, block_f=block_f)
        rows_out = combine_scatter(ys, plan, S * M, jnp.float32)
        # --- reverse exchange + source-side combine -------------------
        back = jax.lax.all_to_all(rows_out.reshape(S, M, d), axis, 0, 0,
                                  tiled=True).reshape(S * M, d)
        val_sorted = jnp.where(kept[:, None],
                               back[jnp.minimum(pos, S * M - 1)], 0.0)
        val = jnp.zeros((N, d), jnp.float32).at[order].set(val_sorted)
        keptf = jnp.zeros((N,), bool).at[order].set(kept)
        # combine in expert-sorted pair order — the exact summation
        # order of the single-device combine_scatter
        eorder = jnp.argsort(jnp.where(live, ec, E))
        contrib = (jnp.where(keptf, wf, 0.0)[:, None] * val)[eorder]
        y = jnp.zeros((T_loc, d), jnp.float32).at[tokl[eorder]].add(
            contrib)
        # --- measured profile -----------------------------------------
        off = (jnp.arange(S) != rix).astype(jnp.int32)
        sent = (send_counts * off).sum()
        recv_off = (recv_counts * off).sum()
        itm = x.dtype.itemsize
        a2a = (sent + recv_off) * d * itm \
            + sent * 4 + 2 * S * 4          # payloads + eids + counts
        tile_rows = plan.tile_valid.sum() * bt
        return (y.astype(x.dtype), recv_counts.sum()[None],
                tile_rows[None], sent[None], a2a[None], send_counts[None])

    # check_vma=False: the Pallas grouped_ffn's out_shape carries no
    # varying-mesh-axes annotation, which the checker would refuse
    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(), P(axis),
                  P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        check_vma=False)

    def run(x, idx, w, w1, w3, w2, hosts, nhosts, local_eids, local_slot):
        w1s = jnp.take(w1, jnp.clip(local_eids, 0, E - 1), axis=0)
        w3s = jnp.take(w3, jnp.clip(local_eids, 0, E - 1), axis=0)
        w2s = jnp.take(w2, jnp.clip(local_eids, 0, E - 1), axis=0)
        return mapped(x, idx, w, hosts, nhosts, local_slot,
                      w1s, w3s, w2s)

    return jax.jit(run)


def _pow2_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class EPExecutor:
    """Driver for the shard_map EP layer: owns a mesh + placement,
    caches compiled variants per (shape, max_rows) configuration, and
    rebalances placement between batches with hysteresis.

    ``__call__`` returns (y, EPStats); ``ffn`` returns y alone (the
    ``expert_ffn(dispatch="ep")`` entry — safe inside an outer jit
    because it never syncs the stats).
    """

    def __init__(self, mesh, placement: Placement, *, axis: str = "model",
                 block_t: Optional[int] = None, block_f: int = 512,
                 use_kernel: Optional[bool] = None,
                 max_rows=None,
                 replicate_hot: int = 0,
                 max_replicas: Optional[int] = None,
                 hysteresis: float = 0.1):
        from repro.kernels.compat import resolve_interpret
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if axis_sizes.get(axis) != placement.num_shards:
            raise ValueError(
                f"mesh axis {axis!r} has size {axis_sizes.get(axis)}, "
                f"placement expects {placement.num_shards} shards")
        self.mesh, self.axis = mesh, axis
        self.placement = placement
        self.block_t = block_t
        self.block_f = block_f
        self.use_kernel = (not resolve_interpret(None)) \
            if use_kernel is None else bool(use_kernel)
        self.max_rows = max_rows
        self.replicate_hot = replicate_hot
        self.max_replicas = max_replicas
        self.hysteresis = hysteresis
        self.rebalances = 0
        self.rebalances_skipped = 0
        self._fns: Dict[tuple, object] = {}

    @classmethod
    def from_config(cls, ep_cfg, num_experts: int, *, mesh=None,
                    load: Optional[np.ndarray] = None,
                    axis: str = "model") -> "EPExecutor":
        """Build an executor from ``configs.base.EPConfig``: makes the
        mesh (``sharding.make_ep_mesh``) unless one is passed, and
        plans the initial placement from ``load`` (gate-histogram
        priors) when given, contiguous otherwise."""
        if mesh is None:
            from repro.sharding import make_ep_mesh
            mesh = make_ep_mesh(ep_cfg.num_shards, axis=axis)
        if load is None:
            pl = contiguous_placement(num_experts, ep_cfg.num_shards)
            if ep_cfg.replicate_hot:
                pl = plan_placement(np.ones(num_experts),
                                    ep_cfg.num_shards,
                                    replicate_hot=ep_cfg.replicate_hot,
                                    max_replicas=ep_cfg.max_replicas)
        else:
            pl = plan_placement(np.asarray(load, np.float64),
                                ep_cfg.num_shards,
                                replicate_hot=ep_cfg.replicate_hot,
                                max_replicas=ep_cfg.max_replicas)
        return cls(mesh, pl, axis=axis, block_t=ep_cfg.block_t,
                   max_rows=ep_cfg.max_rows,
                   replicate_hot=ep_cfg.replicate_hot,
                   max_replicas=ep_cfg.max_replicas,
                   hysteresis=ep_cfg.rebalance_hysteresis)

    # -------------------------------------------------- placement ----

    def update_placement(self, load: np.ndarray) -> bool:
        """Between-batch rebalance from fresh load predictions (e.g.
        ``Scheduler.gate_priors().sum(0)``). Hysteresis means most
        calls are no-ops; a True return implies new weight gathers on
        the next layer call (a recompile only if expert_cap or the
        replica width changed)."""
        new, changed = rebalance(self.placement, load,
                                 replicate_hot=self.replicate_hot,
                                 max_replicas=self.max_replicas,
                                 hysteresis=self.hysteresis)
        if changed:
            self.placement = new
            self.rebalances += 1
        else:
            self.rebalances_skipped += 1
        return changed

    def predicted_peak(self, load: np.ndarray) -> float:
        return placement_peak(self.placement, load)

    # -------------------------------------------------- execution ----

    def _resolve_max_rows(self, idx, w, max_rows, n_loc: int) -> int:
        mr = self.max_rows if max_rows is None else max_rows
        if mr is None:
            return n_loc                      # worst case, always exact
        if mr == "auto":
            counts = exchange_counts(idx, w, self.placement,
                                     mesh=self.mesh, axis=self.axis)
            return min(n_loc, _pow2_bucket(max(1, int(counts.max()))))
        return min(n_loc, int(mr))

    def _fn(self, key):
        if key not in self._fns:
            (M, bt) = key[-2:]
            self._fns[key] = _build_ep_fn(
                self.mesh, self.axis, num_shards=self.placement.num_shards,
                num_experts=self.placement.num_experts,
                expert_cap=self.placement.expert_cap, max_rows=M,
                block_t=bt, block_f=self.block_f,
                use_kernel=self.use_kernel)
        return self._fns[key]

    def __call__(self, x, w1, w3, w2, idx, w, *,
                 max_rows=None) -> Tuple[jnp.ndarray, EPStats]:
        pl = self.placement
        S = pl.num_shards
        T, d = x.shape
        k = idx.shape[1]
        pad = (-T) % S
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
            idx = jnp.concatenate(
                [idx, jnp.full((pad, k), -1, idx.dtype)])
            w = jnp.concatenate([w, jnp.zeros((pad, k), w.dtype)])
        n_loc = (T + pad) // S * k
        M = self._resolve_max_rows(idx, w, max_rows, n_loc)
        bt = self.block_t or default_block_t(S * M, pl.expert_cap)
        key = (T + pad, k, d, w1.shape[-1], pl.expert_cap,
               pl.hosts.shape[1], M, bt)
        fn = self._fn(key)
        y, rows, trows, sent, bytes_, cmat = fn(
            x, idx, w, w1, w3, w2, jnp.asarray(pl.hosts),
            jnp.asarray(pl.nhosts), jnp.asarray(pl.local_eids),
            jnp.asarray(pl.local_slot))
        stats = EPStats(computed_rows=np.asarray(rows),
                        tile_rows=np.asarray(trows),
                        sent_rows=np.asarray(sent),
                        a2a_bytes=np.asarray(bytes_),
                        count_matrix=np.asarray(cmat),
                        max_rows=M)
        return y[:T], stats

    def ffn(self, x, w1, w3, w2, idx, w) -> jnp.ndarray:
        """y alone, no host sync — usable inside an outer jit (the
        ``dispatch="ep"`` model path). max_rows resolves statically
        (never "auto": that needs a host round-trip)."""
        pl = self.placement
        S = pl.num_shards
        T, d = x.shape
        k = idx.shape[1]
        pad = (-T) % S
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)])
            idx = jnp.concatenate(
                [idx, jnp.full((pad, k), -1, idx.dtype)])
            w = jnp.concatenate([w, jnp.zeros((pad, k), w.dtype)])
        n_loc = (T + pad) // S * k
        mr = self.max_rows
        M = n_loc if (mr is None or mr == "auto") else min(n_loc, int(mr))
        bt = self.block_t or default_block_t(S * M, pl.expert_cap)
        key = (T + pad, k, d, w1.shape[-1], pl.expert_cap,
               pl.hosts.shape[1], M, bt)
        y = self._fn(key)(
            x, idx, w, w1, w3, w2, jnp.asarray(pl.hosts),
            jnp.asarray(pl.nhosts), jnp.asarray(pl.local_eids),
            jnp.asarray(pl.local_slot))[0]
        return y[:T]
