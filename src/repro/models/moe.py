"""Mixture-of-Experts FFN layer with XShare batch-aware selection as a
first-class routing policy.

Expert compute routes through a ``dispatch`` switch (see expert_ffn):

  sorted — the default hot path: argsort token-expert pairs by expert,
           grouped GEMM over occupied expert segments, scatter-combine
           (models/dispatch.py + kernels/moe_ffn.py grouped_ffn).
           Capacity-free; compute and weight traffic scale with the
           experts XShare actually selected, not with E.
  einsum — the GShard capacity-based dense dispatch/combine einsums,
           retained as the reference semantics: with the expert axis
           sharded over the mesh "model" axis the (G, t, E, C) one-hot
           einsums lower to all-to-all.
  dense  — decode-sized fast path: every expert runs on every token and
           the combine weights zero the unselected (per-op-overhead
           bound regime, T <= 32).

The paper's algorithms plug in between the router softmax and the
dispatch: they shrink the *set* of experts any token may route to,
which on the EP mesh bounds the per-shard load (Alg 5/6) and in the
Pallas serving kernels skips inactive experts' HBM->VMEM weight
streaming entirely (kernels/moe_ffn.py).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig, XSharePolicy
from repro.core import metrics as M
from repro.core import selection
from repro.core.routing import topk_route
from repro.models import dispatch as DSP
from repro.models.layers import dense_init, mlp_apply
from repro.sharding import constrain, current_mesh

OFF = XSharePolicy(mode="off")

DISPATCH_MODES = ("auto", "sorted", "einsum", "dense", "ep")


def policy_max_active(policy: XSharePolicy, num_tokens: int,
                      num_experts: int, *,
                      spec_shape: Optional[Tuple[int, int]] = None) -> int:
    """Static upper bound on |selected expert set| under a policy — the
    XShare budget the sorted path's padded buffer / tile count (and on
    TPU its weight HBM traffic) scales with."""
    E, T = num_experts, num_tokens
    if policy.mode == "batch":
        return min(E, policy.k0 * T + policy.m_l)
    if policy.mode == "ep":
        bound = policy.num_groups * policy.m_g
        if not policy.strict_cap:
            bound += policy.k0 * T
        return min(E, bound)
    if policy.mode == "spec" and spec_shape is not None:
        b, t = spec_shape
        return min(E, b * (policy.k0 * t + policy.m_r) + policy.m_l)
    return E


def init_moe(key, moe: MoEConfig, d_model: int, dtype,
             stack: Optional[int] = None) -> Dict:
    pre = () if stack is None else (stack,)
    ks = jax.random.split(key, 7)
    E, f = moe.num_experts, moe.d_ff_expert
    p = {
        "wg": dense_init(ks[0], pre + (d_model, E), jnp.float32),
        "w1": dense_init(ks[1], pre + (E, d_model, f), dtype),
        "w3": dense_init(ks[2], pre + (E, d_model, f), dtype),
        "w2": dense_init(ks[3], pre + (E, f, d_model), dtype),
    }
    if moe.num_shared_experts:
        fs = moe.d_ff_shared * moe.num_shared_experts
        p["ws1"] = dense_init(ks[4], pre + (d_model, fs), dtype)
        p["ws3"] = dense_init(ks[5], pre + (d_model, fs), dtype)
        p["ws2"] = dense_init(ks[6], pre + (fs, d_model), dtype)
    return p


def route(p: Dict, x: jnp.ndarray, moe: MoEConfig, policy: XSharePolicy,
          spec_shape: Optional[Tuple[int, int]] = None,
          token_mask: Optional[jnp.ndarray] = None,
          spec_priors: Optional[jnp.ndarray] = None):
    """Router + XShare selection. x: (T, d).

    token_mask: optional (T,) bool — masked-out tokens (inactive
    continuous-batching slots) are dropped from routing entirely: their
    gate mass is zeroed before XShare batch aggregation, their expert
    index becomes -1 (a zero one-hot), so they consume no dispatch
    capacity and never count as activating an expert.

    spec_priors: optional (b, E) per-request gate-histogram priors for
    mode="spec" correlation-aware selection (b = spec_shape[0]).

    Returns (idx (T,k), weights (T,k), combine (T,E) f32, aux dict).
    The combine matrix (gate weight per token-expert cell) is built
    exactly once here and reused by every downstream consumer — the
    dense dispatch path, the Pallas masked-FFN kernel, and the aux
    metrics — instead of each rebuilding the (T, k, E) one-hot.
    """
    logits = jnp.asarray(x, jnp.float32) @ jnp.asarray(p["wg"], jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if token_mask is not None:
        probs = probs * token_mask[:, None].astype(probs.dtype)
    if policy.mode == "off":
        idx, w = topk_route(logits, moe.top_k, normalize=moe.normalize_gates)
        mask = jnp.ones((moe.num_experts,), bool)
    else:
        idx, w, mask = selection.apply_policy(
            probs, policy, top_k=moe.top_k, spec_shape=spec_shape,
            logits=logits, priors=spec_priors)
    if token_mask is not None:
        idx = jnp.where(token_mask[:, None], idx, -1)
        w = jnp.where(token_mask[:, None], w, 0.0)
    one_hot = jax.nn.one_hot(idx, moe.num_experts, dtype=w.dtype)
    combine = (one_hot * w[..., None]).sum(axis=-2)       # (T, E)
    active = (combine > 0).any(axis=0)
    # group math handles E % G != 0 (ceil-width groups, last smaller),
    # so no divisibility fallback: aux loads always reflect G shards
    G = policy.num_groups
    # Switch-Transformer load-balance auxiliary: E * sum_e f_e * P_e
    # (f_e = fraction of tokens routed to e, P_e = mean router prob).
    # Real MoEs train with this — without it the router collapses and
    # the batch-activation statistics the paper studies never appear.
    # masked rows are zeroed above, so sums only see live tokens — but
    # the mean must divide by the live-token count, not T, or lb_loss
    # deflates as the running batch empties
    denom = probs.shape[0] if token_mask is None else \
        jnp.maximum(token_mask.sum(), 1).astype(jnp.float32)
    frac = (one_hot.sum(-2) > 0).astype(jnp.float32).sum(0) / denom  # (E,)
    lb = moe.num_experts * (frac * (probs.sum(0) / denom)).sum() / moe.top_k
    # real per-expert segment sizes (what each EP shard computes under
    # sorted dispatch) — not the E/G * C rows capacity padding implies
    counts = jnp.zeros((moe.num_experts,), jnp.int32).at[idx].add(
        (w != 0.0).astype(jnp.int32), mode="drop")
    aux = {
        "activated_experts": active.sum(),
        "selected_set": mask.sum(),
        "max_group_load": M.max_group_load(active, G),
        "max_group_tokens": DSP.group_token_loads(counts, G).max(),
        "gate_mass": M.gate_mass_captured(probs, mask),
        "lb_loss": lb,
    }
    if spec_shape is not None:
        # per-request gate histogram over this pass's live tokens — the
        # raw material for the scheduler's correlation priors (fed back
        # as spec_priors on later rounds). masked rows were zeroed above,
        # so the mean divides by each request's live-token count.
        b, t = spec_shape
        pr = probs.reshape(b, t, probs.shape[-1])
        if token_mask is not None:
            denom_r = jnp.maximum(
                token_mask.reshape(b, t).sum(-1, keepdims=True), 1)
        else:
            denom_r = jnp.full((b, 1), t)
        aux["req_gate_hist"] = pr.sum(axis=1) / denom_r      # (b, E)
    return idx, w, combine, aux


def einsum_capacity(tokens_per_group: int, top_k: int, num_experts: int,
                    capacity_factor: float, *, min_capacity: int = 4,
                    capacity: Optional[int] = None) -> int:
    """Per-expert per-group buffer size C of the einsum dispatch path —
    the one place the GShard capacity rule lives (benchmarks derive
    their byte models from here, not from a copy)."""
    t = tokens_per_group
    if capacity is not None:
        return min(capacity, t)
    c = max(min_capacity,
            int(-(-t * top_k * capacity_factor // num_experts)))
    return min(c, t)


def expert_ffn(p: Dict, x: jnp.ndarray, idx: jnp.ndarray, w: jnp.ndarray,
               moe: MoEConfig, *, capacity_factor: float = 1.25,
               min_capacity: int = 4,
               capacity: Optional[int] = None,
               group_size: int = 2048,
               dispatch: str = "auto",
               combine: Optional[jnp.ndarray] = None,
               max_active: Optional[int] = None) -> jnp.ndarray:
    """Routed-expert compute behind the dispatch switch.

    x: (T, d); idx/w: (T, k); combine: optional (T, E) gate matrix from
    route() (reused by the dense path instead of rebuilding the one-hot).

    dispatch:
      "sorted" — argsort pairs by expert, grouped GEMM over occupied
                 segments (Pallas grouped_ffn on TPU, tile-gather einsum
                 elsewhere), scatter-combine. Capacity-free unless
                 ``capacity`` is given (then per-expert clamp, first
                 tokens kept — the EP load bound). max_active bounds the
                 padded layout by the XShare budget.
      "einsum" — GShard (G, t, E, C) one-hot dispatch/combine einsums,
                 tokens in G groups of t <= group_size, per-group
                 capacity C = max(min_capacity, ceil(t*k/E * cf)).
                 Tokens beyond capacity are dropped; capacity=t is
                 drop-free (requires G == 1 to be truly global). The
                 reference semantics; on an EP mesh the einsums lower
                 to all-to-all.
      "dense"  — every expert on every token, combine weights zero the
                 unselected. Cheapest at decode sizes where per-op
                 overhead dominates; only off-mesh (it would all-gather
                 every expert's weights onto each device).
      "ep"     — real expert-parallel execution through the EPExecutor
                 bound via ``repro.ep.ep_context``: per-shard sort,
                 ragged all-to-all row exchange, local grouped GEMM on
                 placement-assigned experts, reverse exchange + combine
                 (ep/executor.py). Agrees with "sorted" to within
                 ep.EP_TOL_EPS; raises when no executor is bound.
      "auto"   — dense for decode-sized drop-free batches off-mesh,
                 sorted otherwise.
    """
    T, d = x.shape
    E, k = moe.num_experts, idx.shape[-1]
    assert dispatch in DISPATCH_MODES, dispatch
    if dispatch == "ep":
        from repro import ep as EP
        ex = EP.current_executor()
        if ex is None:
            raise ValueError('dispatch="ep" needs an EPExecutor bound via '
                             "repro.ep.ep_context")
        return ex.ffn(x, p["w1"], p["w3"], p["w2"], idx, w).astype(x.dtype)
    G = 1
    if T > group_size:
        for cand in range(T // group_size, 0, -1):
            if T % cand == 0 and T // cand <= group_size:
                G = cand
                break
    t = T // G
    C = einsum_capacity(t, k, E, capacity_factor,
                        min_capacity=min_capacity, capacity=capacity)

    if dispatch == "auto":
        if G == 1 and C >= T and T <= 32 and current_mesh() is None:
            dispatch = "dense"
        else:
            dispatch = "sorted"

    if dispatch == "dense":
        E_, f = E, p["w1"].shape[-1]
        if combine is None:
            one_hot = jax.nn.one_hot(idx, E_, dtype=jnp.float32)
            combine = (one_hot * w[..., None].astype(jnp.float32)).sum(-2)
        gate = combine                                    # (T, E)
        # flat GEMMs (XLA CPU/TPU handle one (T, E*f) dot far better
        # than E tiny batched matmuls); gate folds in before w2 — same
        # sum, one fewer (T,E,d) intermediate
        w1f = p["w1"].transpose(1, 0, 2).reshape(d, E_ * f)
        w3f = p["w3"].transpose(1, 0, 2).reshape(d, E_ * f)
        h = (x @ w1f).reshape(T, E_, f)
        h = jax.nn.silu(h) * (x @ w3f).reshape(T, E_, f)
        hg = (h * gate[:, :, None].astype(h.dtype)).reshape(T, E_ * f)
        return (hg @ p["w2"].reshape(E_ * f, d)).astype(x.dtype)

    if dispatch == "sorted":
        return DSP.sorted_expert_ffn(
            x, p["w1"], p["w3"], p["w2"], idx, w,
            capacity=capacity, max_active=max_active)

    xg = x.reshape(G, t, d)
    one_hot = jax.nn.one_hot(idx.reshape(G, t, k), E, dtype=jnp.float32)
    gate = (one_hot * w.reshape(G, t, k)[..., None].astype(jnp.float32)
            ).sum(-2)                                      # (G,t,E)
    routed = one_hot.sum(-2)                               # (G,t,E) 0/1
    # position of token within its expert's per-group buffer
    pos = (jnp.cumsum(routed, axis=1) - routed).astype(jnp.int32)  # (G,t,E)
    keep = routed * (pos < C)
    disp = keep[..., None] * jax.nn.one_hot(pos, C, dtype=jnp.float32)
    disp = constrain(disp, "batch", None, "model", None)   # (G,t,E,C)
    xe = jnp.einsum("gtec,gtd->gecd", disp, jnp.asarray(xg, jnp.float32))
    xe = constrain(xe.astype(x.dtype), "batch", "model", None, None)
    h = jnp.einsum("gecd,edf->gecf", xe, p["w1"])
    h = jax.nn.silu(h) * jnp.einsum("gecd,edf->gecf", xe, p["w3"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w2"])          # (G,E,C,d)
    ye = constrain(ye, "batch", "model", None, None)
    comb = disp * gate[..., None]                          # (G,t,E,C)
    y = jnp.einsum("gtec,gecd->gtd", comb, jnp.asarray(ye, jnp.float32))
    y = constrain(y, "batch", None, None)
    return y.reshape(T, d).astype(x.dtype)


def moe_apply(p: Dict, x: jnp.ndarray, moe: MoEConfig,
              policy: XSharePolicy = OFF, *,
              spec_shape: Optional[Tuple[int, int]] = None,
              capacity_factor: float = 1.25,
              capacity: Optional[int] = None,
              token_mask: Optional[jnp.ndarray] = None,
              dispatch: str = "auto",
              spec_priors: Optional[jnp.ndarray] = None):
    """Full MoE layer. x: (..., d) (leading dims flattened internally).

    token_mask: optional bool array matching x's leading dims — tokens
    masked False are excluded from routing (see route()).

    dispatch: expert-compute path, see expert_ffn. The XShare budget
    bound (policy_max_active) sizes the sorted path's padded layout.

    spec_priors: optional (b, E) correlation priors for mode="spec"
    (see route()).

    Returns (y, aux). Shared experts (DeepSeek-style) are added
    unconditionally — they are outside the selection problem (Sec 2.1).
    """
    shape = x.shape
    xt = x.reshape(-1, shape[-1])
    tm = None if token_mask is None else token_mask.reshape(-1)
    idx, w, combine, aux = route(p, xt, moe, policy, spec_shape,
                                 token_mask=tm, spec_priors=spec_priors)
    ma = policy_max_active(policy, xt.shape[0], moe.num_experts,
                           spec_shape=spec_shape)
    y = expert_ffn(p, xt, idx, w, moe, capacity_factor=capacity_factor,
                   capacity=capacity, dispatch=dispatch, combine=combine,
                   max_active=ma)
    if "ws1" in p:
        y = y + mlp_apply({"w1": p["ws1"], "w3": p["ws3"], "w2": p["ws2"]},
                          xt, "swiglu")
    return y.reshape(shape), aux
