"""Model composition: embed -> scanned decoder blocks -> head, for all six
assigned architecture families (dense / moe / ssm / hybrid / vlm / audio).

Layers are stacked on a leading (L, ...) axis and consumed with lax.scan
(compile time stays flat in depth — required for the 94-layer MoE).
The hybrid (Zamba2) family interleaves a *weight-shared* attention block
every `attn_every` SSM layers via a Python loop over groups, each group
scanning its slice of the stacked SSM params.

Three entry points mirror the assigned input shapes:
  forward      — full-sequence, no cache (train_4k)
  prefill      — full-sequence, builds the decode cache (prefill_32k)
  decode_step  — T new tokens (T=1 decode, T=1+L_s speculative verify)
                 against the cache (decode_32k / long_500k)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, XSharePolicy
from repro.models import attention as A
from repro.models import ssm as S
from repro.models.layers import (cross_entropy, dense_init, mlp_apply,
                                 mlp_init, rms_norm)
from repro.models.moe import OFF, init_moe, moe_apply
from repro.sharding import constrain

WINDOW_MARGIN = 512  # rolling-cache slack: spec-verify never overwrites
                     # in-window slots (needs >= spec_len; see
                     # attention.py), and window+margin stays divisible
                     # by every mesh-axis extent (16/256/512) so the
                     # cache sequence dim shards cleanly.


# ----------------------------------------------------------------- init ---

def init_params(cfg: ArchConfig, key, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 10)
    L, d, V = cfg.num_layers, cfg.d_model, cfg.padded_vocab
    params: Dict = {}
    if cfg.family == "audio":
        params["embed"] = dense_init(ks[0], (cfg.num_codebooks, V, d), dtype)
    else:
        params["embed"] = dense_init(ks[0], (V, d), dtype)

    layers: Dict = {}
    if cfg.family in ("dense", "vlm", "audio"):
        layers["attn_norm"] = jnp.ones((L, d), dtype)
        layers["attn"] = A.init_attn(ks[1], cfg.attn, d, dtype, stack=L)
        layers["mlp_norm"] = jnp.ones((L, d), dtype)
        layers["mlp"] = mlp_init(ks[2], d, cfg.d_ff, dtype, cfg.act, stack=L)
    elif cfg.family == "moe":
        layers["attn_norm"] = jnp.ones((L, d), dtype)
        layers["attn"] = A.init_attn(ks[1], cfg.attn, d, dtype, stack=L)
        layers["moe_norm"] = jnp.ones((L, d), dtype)
        layers["moe"] = init_moe(ks[2], cfg.moe, d, dtype, stack=L)
    elif cfg.family in ("ssm", "hybrid"):
        layers["norm"] = jnp.ones((L, d), dtype)
        layers["ssm"] = S.init_ssm(ks[1], cfg.ssm, d, dtype, stack=L)
    else:
        raise ValueError(cfg.family)
    params["layers"] = layers

    if cfg.family == "hybrid":
        params["shared_attn"] = {
            "attn_norm": jnp.ones((d,), dtype),
            "attn": A.init_attn(ks[3], cfg.attn, d, dtype),
            "mlp_norm": jnp.ones((d,), dtype),
            "mlp": mlp_init(ks[4], d, cfg.d_ff, dtype, cfg.act),
        }
    params["final_norm"] = jnp.ones((d,), dtype)
    if not cfg.tie_embeddings:
        if cfg.family == "audio":
            params["lm_head"] = dense_init(ks[5], (cfg.num_codebooks, d, V),
                                           dtype)
        else:
            params["lm_head"] = dense_init(ks[5], (d, V), dtype)
    return params


def param_count(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------- embed / head --

def embed_tokens(cfg: ArchConfig, params, tokens: jnp.ndarray) -> jnp.ndarray:
    if cfg.family == "audio":
        # tokens (B, S, K): sum of per-codebook embeddings
        parts = [jnp.take(params["embed"][k], tokens[..., k], axis=0)
                 for k in range(cfg.num_codebooks)]
        return sum(parts)
    return jnp.take(params["embed"], tokens, axis=0)


@jax.named_scope("lm_head")
def lm_head_apply(cfg: ArchConfig, params, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.family == "audio":
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,kvd->bskv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,kdv->bskv", x, params["lm_head"])
    else:
        table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ table
    if cfg.padded_vocab != cfg.vocab_size:
        pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad, -1e30, logits)
    return logits


# ------------------------------------------------------------ block fns ---

@jax.named_scope("attention")
def _attn_block_full(cfg: ArchConfig, lp: Dict, x: jnp.ndarray,
                     positions: jnp.ndarray,
                     window: Optional[int]) -> jnp.ndarray:
    """Pre-norm attention sub-block, full sequence. Returns residual-added x."""
    B, T = x.shape[:2]
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = A.qkv_project(lp["attn"], h, positions, cfg.attn, cfg.norm_eps)
    a = A.flash_attention(q, k, v, causal=True, window=window)
    return x + a.reshape(B, T, -1) @ lp["attn"]["wo"]


@jax.named_scope("attention")
def _attn_block_decode(cfg: ArchConfig, lp: Dict, x: jnp.ndarray,
                       positions: jnp.ndarray, kv_k, kv_v, layer, cur_len,
                       window: Optional[int]):
    """kv_k / kv_v: the whole stacked (L, B, C, Hkv, dh) cache, carried
    through the layer scan. Slice `layer` is rewritten in place and
    attention reads it back from the carried buffer after the write, so
    XLA keeps one cache buffer: reading the value update_cache returned
    instead makes it copy the stacked cache to guard the old slice."""
    B, T = x.shape[:2]
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q, k, v = A.qkv_project(lp["attn"], h, positions, cfg.attn, cfg.norm_eps)

    def write(stack, new):
        old = jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False)
        upd = A.update_cache(old, new, cur_len, window=window)
        return jax.lax.dynamic_update_index_in_dim(stack, upd, layer, 0)

    with jax.named_scope("kv_write"):
        kv_k = write(kv_k, k)
        kv_v = write(kv_v, v)
    ck = jax.lax.dynamic_index_in_dim(kv_k, layer, keepdims=False)
    cv = jax.lax.dynamic_index_in_dim(kv_v, layer, keepdims=False)
    a = A.cached_attention(q, ck, cv, cur_len, window=window)
    return x + a.reshape(B, T, -1) @ lp["attn"]["wo"], kv_k, kv_v


def _ffn_block(cfg: ArchConfig, lp: Dict, x: jnp.ndarray,
               policy: XSharePolicy, spec_shape, capacity,
               capacity_factor: float,
               token_mask: Optional[jnp.ndarray] = None,
               dispatch: str = "auto",
               spec_priors: Optional[jnp.ndarray] = None):
    if cfg.family == "moe":
        h = rms_norm(x, lp["moe_norm"], cfg.norm_eps)
        y, aux = moe_apply(lp["moe"], h, cfg.moe, policy,
                           spec_shape=spec_shape, capacity=capacity,
                           capacity_factor=capacity_factor,
                           token_mask=token_mask, dispatch=dispatch,
                           spec_priors=spec_priors)
        return x + y, aux
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    return x + mlp_apply(lp["mlp"], h, cfg.act), {}


def _shared_attn_block(cfg: ArchConfig, sp: Dict, x: jnp.ndarray,
                       positions: jnp.ndarray, window: Optional[int],
                       cache=None, cur_len=None):
    """Hybrid family's weight-shared attention+MLP block."""
    B, T = x.shape[:2]
    h = rms_norm(x, sp["attn_norm"], cfg.norm_eps)
    q, k, v = A.qkv_project(sp["attn"], h, positions, cfg.attn, cfg.norm_eps)
    new_cache = None
    if cache is None:
        a = A.flash_attention(q, k, v, causal=True, window=window)
    else:
        ck, cv = cache
        ck = A.update_cache(ck, k, cur_len, window=window)
        cv = A.update_cache(cv, v, cur_len, window=window)
        a = A.cached_attention(q, ck, cv, cur_len, window=window)
        new_cache = (ck, cv)
    x = x + a.reshape(B, T, -1) @ sp["attn"]["wo"]
    h = rms_norm(x, sp["mlp_norm"], cfg.norm_eps)
    x = x + mlp_apply(sp["mlp"], h, cfg.act)
    return x, new_cache


def _num_shared_apps(cfg: ArchConfig) -> int:
    return -(-cfg.num_layers // cfg.attn_every) if cfg.attn_every else 0


# -------------------------------------------------------------- forward ---

def _backbone(cfg: ArchConfig, params, tokens: jnp.ndarray, *,
              prefix_embeds: Optional[jnp.ndarray] = None,
              policy: XSharePolicy = OFF,
              spec_shape: Optional[Tuple[int, int]] = None,
              remat: bool = False,
              window: Optional[int] = None,
              capacity: Optional[int] = None,
              capacity_factor: float = 1.25,
              dispatch: str = "auto"):
    """Full-sequence backbone. Returns (final-normed hidden states, aux).

    window overrides cfg.attn.sliding_window (forced-window long-context
    variant); prefix_embeds (B, P, d) are prepended (vlm/audio stubs).
    """
    x = embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, T = x.shape[:2]
    positions = jnp.arange(T)[None, :].repeat(B, axis=0)
    eff_window = window if window is not None else (
        cfg.attn.sliding_window if cfg.attn else None)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def layer(h, lp):
            # sequence parallelism: the residual stream (and thus the
            # remat checkpoint stack) lives sharded (batch, seq/model);
            # XLA inserts all-gather before attn / reduce-scatter after
            h = constrain(h, "batch", "model", None, tag="seqpar")
            h = _attn_block_full(cfg, lp, h, positions, eff_window)
            h, aux = _ffn_block(cfg, lp, h, policy, spec_shape, capacity,
                                capacity_factor, dispatch=dispatch)
            return h, aux
        f = jax.checkpoint(layer) if remat else layer
        x, aux = jax.lax.scan(f, x, params["layers"])
    elif cfg.family == "ssm":
        def layer(h, lp):
            h = constrain(h, "batch", "model", None, tag="seqpar")   # sequence parallel
            hn = rms_norm(h, lp["norm"], cfg.norm_eps)
            y, _ = S.ssm_forward(lp["ssm"], hn, cfg.ssm, cfg.d_model,
                                 cfg.norm_eps)
            return h + y, None
        f = jax.checkpoint(layer) if remat else layer
        x, aux = jax.lax.scan(f, x, params["layers"])
    elif cfg.family == "hybrid":
        ae = cfg.attn_every
        def layer(h, lp):
            h = constrain(h, "batch", "model", None, tag="seqpar")   # sequence parallel
            hn = rms_norm(h, lp["norm"], cfg.norm_eps)
            y, _ = S.ssm_forward(lp["ssm"], hn, cfg.ssm, cfg.d_model,
                                 cfg.norm_eps)
            return h + y, None
        f = jax.checkpoint(layer) if remat else layer
        for g in range(_num_shared_apps(cfg)):
            x, _ = _shared_attn_block(cfg, params["shared_attn"], x,
                                      positions, eff_window)
            lo, hi = g * ae, min((g + 1) * ae, cfg.num_layers)
            gp = jax.tree_util.tree_map(lambda a: a[lo:hi], params["layers"])
            x, _ = jax.lax.scan(f, x, gp)
        aux = None
    else:
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, (aux if isinstance(aux, dict) else {})


def forward(cfg: ArchConfig, params, tokens: jnp.ndarray, **kw):
    """Full-sequence forward. Returns (logits over all positions, aux)."""
    x, aux = _backbone(cfg, params, tokens, **kw)
    return lm_head_apply(cfg, params, x), aux


def _fused_head_ce(cfg: ArchConfig, params, x: jnp.ndarray,
                   targets: jnp.ndarray, chunk: int = 512) -> jnp.ndarray:
    """Head projection + cross-entropy fused over sequence chunks with
    per-chunk remat: the full (B, S, V) f32 logits tensor (gigabytes at
    128k-256k vocab) never materializes, forward or backward."""
    B, Sx = x.shape[0], x.shape[1]
    c = min(chunk, Sx)
    n = -(-Sx // c)
    pad = n * c - Sx
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        pad_t = ((0, 0), (0, pad)) + ((0, 0),) * (targets.ndim - 2)
        targets = jnp.pad(targets, pad_t)
    valid = (jnp.arange(n * c) < Sx)

    xs = x.reshape(B, n, c, -1).transpose(1, 0, 2, 3)
    ts = targets.reshape((B, n, c) + targets.shape[2:]).transpose(
        (1, 0, 2) + tuple(range(3, targets.ndim + 1)))
    ms = valid.reshape(n, c)

    @jax.checkpoint
    def chunk_fn(carry, inp):
        xc, tc, mc = inp
        logits = lm_head_apply(cfg, params, xc)       # (B,c,V[,K..])
        logits = jnp.asarray(logits, jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = logz - gold                             # (B,c[,K])
        if nll.ndim == 3:                             # audio codebooks
            nll = nll.mean(-1)
        mcf = mc[None, :].astype(jnp.float32)
        return (carry[0] + (nll * mcf).sum(), carry[1] + mcf.sum() * B), None

    (tot, cnt), _ = jax.lax.scan(chunk_fn, (jnp.zeros(()), jnp.zeros(())),
                                 (xs, ts, ms))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(cfg: ArchConfig, params, tokens: jnp.ndarray, *,
            prefix_embeds: Optional[jnp.ndarray] = None,
            policy: XSharePolicy = OFF, remat: bool = True,
            capacity_factor: float = 1.25,
            lb_weight: float = 0.02):
    """Mean next-token cross-entropy (prefix positions excluded), via the
    fused chunked head+CE, plus the MoE load-balance auxiliary."""
    x, aux = _backbone(cfg, params, tokens, prefix_embeds=prefix_embeds,
                       policy=policy, remat=remat,
                       capacity_factor=capacity_factor)
    P = 0 if prefix_embeds is None else prefix_embeds.shape[1]
    # hidden at position P+i predicts tokens[:, i+1]
    loss = _fused_head_ce(cfg, params, x[:, P:-1], tokens[:, 1:])
    if lb_weight and isinstance(aux, dict) and "lb_loss" in aux:
        loss = loss + lb_weight * jnp.mean(aux["lb_loss"])
    return loss, aux


# ---------------------------------------------------------------- cache ---

def effective_window(cfg: ArchConfig, *, force_window: Optional[int] = None
                     ) -> Optional[int]:
    if force_window is not None:
        return force_window
    return cfg.attn.sliding_window if cfg.attn else None


def init_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype, *,
               force_window: Optional[int] = None) -> Dict:
    """Decode cache pytree. cache_len must include room for new tokens
    (spec verify) when no window is set.

    ``cur_len`` is a per-slot (batch,) vector — the universal cache
    representation: lockstep decode advances every row together,
    speculative decode rolls rows back raggedly, and the continuous-
    batching scheduler (serving/scheduler.py) gives every slot an
    independent lifetime via insert_request / evict_slot below.
    """
    L, d = cfg.num_layers, cfg.d_model
    cache: Dict = {"cur_len": jnp.zeros((batch,), jnp.int32)}
    win = effective_window(cfg, force_window=force_window)
    C = (win + WINDOW_MARGIN) if win is not None else cache_len

    def kv(n_stack):
        a = cfg.attn
        shape = (n_stack, batch, C, a.num_kv_heads, a.head_dim)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        cache["kv_k"], cache["kv_v"] = kv(L)
    if cfg.family in ("ssm", "hybrid"):
        d_inner, nh, d_bc = S.dims(cfg.ssm, d)
        K = cfg.ssm.d_conv
        cache["conv_x"] = jnp.zeros((L, batch, K - 1, d_inner), dtype)
        cache["conv_B"] = jnp.zeros((L, batch, K - 1, d_bc), dtype)
        cache["conv_C"] = jnp.zeros((L, batch, K - 1, d_bc), dtype)
        cache["state"] = jnp.zeros(
            (L, batch, nh, cfg.ssm.head_dim, cfg.ssm.d_state), jnp.float32)
    if cfg.family == "hybrid":
        cache["shared_k"], cache["shared_v"] = kv(_num_shared_apps(cfg))
    return cache


# Every stacked cache array carries batch on axis 1 (leading axis is the
# layer / shared-block stack); cur_len is the lone per-slot (B,) vector.
_CACHE_BATCH_AXIS = 1


def insert_request(cache: Dict, req_cache: Dict, slot, src=0) -> Dict:
    """Cache surgery: copy row `src` of a prefilled cache (batch >= 1 —
    the scheduler batch-prefills simultaneous admissions) into row `slot`
    of a running batch cache.

    The whole per-slot extent (full cache sequence axis included) is
    overwritten, so whatever a previous occupant — or a compute-masked
    empty slot — left behind is erased. `slot` / `src` may be Python ints
    or traced scalars, so one jitted copy serves every (slot, src) pair.
    """
    out = {}
    for k, v in cache.items():
        if k == "cur_len":
            r = jax.lax.dynamic_slice_in_dim(req_cache[k], src, 1, axis=0)
            out[k] = jax.lax.dynamic_update_slice(v, r.astype(v.dtype),
                                                  (slot,))
        else:
            r = jax.lax.dynamic_slice_in_dim(req_cache[k], src, 1,
                                             axis=_CACHE_BATCH_AXIS)
            start = (0, slot) + (0,) * (v.ndim - _CACHE_BATCH_AXIS - 1)
            out[k] = jax.lax.dynamic_update_slice(v, r.astype(v.dtype),
                                                  start)
    return out


def evict_slot(cache: Dict, slot, *, scrub: bool = False) -> Dict:
    """Cache surgery: mark row `slot` free (cur_len = 0).

    KV / state contents are left in place — they are dead weight until
    insert_request overwrites the row, and the scheduler compute-masks
    evicted slots so they never influence live requests.

    scrub=True (static) additionally zeroes the slot's every cache
    array: numerics quarantine evicts poisoned requests this way so
    non-finite values cannot outlive the request through any path the
    compute mask doesn't cover.
    """
    out = {}
    for k, v in cache.items():
        if k == "cur_len":
            out[k] = jax.lax.dynamic_update_slice(
                v, jnp.zeros((1,), v.dtype), (slot,))
        elif scrub:
            row = jnp.zeros((v.shape[0], 1) + v.shape[2:], v.dtype)
            start = (0, slot) + (0,) * (v.ndim - _CACHE_BATCH_AXIS - 1)
            out[k] = jax.lax.dynamic_update_slice(v, row, start)
        else:
            out[k] = v
    return out


# -------------------------------------------------------------- prefill ---

def _build_cache_slice(k: jnp.ndarray, C: int, win: Optional[int]
                       ) -> jnp.ndarray:
    """Arrange full-sequence kv (B,S,Hkv,dh) into a cache buffer (B,C,...)."""
    B, Ss = k.shape[0], k.shape[1]
    if win is None:
        assert Ss <= C, (Ss, C)
        buf = jnp.zeros((B, C) + k.shape[2:], k.dtype)
        return jax.lax.dynamic_update_slice(buf, k, (0, 0, 0, 0))
    n = min(Ss, C)
    tail = k[:, Ss - n:]
    slots = (jnp.arange(Ss - n, Ss)) % C
    buf = jnp.zeros((B, C) + k.shape[2:], k.dtype)
    return buf.at[:, slots].set(tail)


def prefill(cfg: ArchConfig, params, tokens: jnp.ndarray, *,
            cache_len: int,
            prefix_embeds: Optional[jnp.ndarray] = None,
            policy: XSharePolicy = OFF,
            force_window: Optional[int] = None,
            cache_dtype=None,
            capacity_factor: float = 1.25,
            dispatch: str = "auto"):
    """Process the prompt, build the decode cache. Returns
    (last-position logits (B, V[,K]), cache, aux)."""
    x = embed_tokens(cfg, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, T = x.shape[:2]
    positions = jnp.arange(T)[None, :].repeat(B, axis=0)
    win = effective_window(cfg, force_window=force_window)
    C = (win + WINDOW_MARGIN) if win is not None else cache_len
    cdt = cache_dtype or x.dtype

    cache = init_cache(cfg, B, cache_len, cdt, force_window=force_window)
    cache["cur_len"] = jnp.full((B,), T, jnp.int32)

    if cfg.family in ("dense", "vlm", "audio", "moe"):
        def layer(h, lp):
            h = constrain(h, "batch", "model", None, tag="seqpar")   # sequence parallel
            with jax.named_scope("attention"):
                hn = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
                q, k, v = A.qkv_project(lp["attn"], hn, positions, cfg.attn,
                                        cfg.norm_eps)
                a = A.flash_attention(q, k, v, causal=True, window=win)
                h = h + a.reshape(B, T, -1) @ lp["attn"]["wo"]
            h, aux = _ffn_block(cfg, lp, h, policy, None, None,
                                capacity_factor, dispatch=dispatch)
            with jax.named_scope("attention"), jax.named_scope("kv_write"):
                ck = _build_cache_slice(k, C, win).astype(cdt)
                cv = _build_cache_slice(v, C, win).astype(cdt)
            return h, (ck, cv, aux)
        x, (cks, cvs, aux) = jax.lax.scan(layer, x, params["layers"])
        cache["kv_k"], cache["kv_v"] = cks, cvs
    elif cfg.family == "ssm":
        def layer(h, lp):
            h = constrain(h, "batch", "model", None, tag="seqpar")   # sequence parallel
            hn = rms_norm(h, lp["norm"], cfg.norm_eps)
            y, (conv, state) = S.ssm_forward(lp["ssm"], hn, cfg.ssm,
                                             cfg.d_model, cfg.norm_eps)
            conv = tuple(c.astype(cdt) for c in conv)
            return h + y, (conv, state)
        x, (convs, states) = jax.lax.scan(layer, x, params["layers"])
        cache["conv_x"], cache["conv_B"], cache["conv_C"] = convs
        cache["state"] = states
        aux = {}
    elif cfg.family == "hybrid":
        ae = cfg.attn_every
        def layer(h, lp):
            h = constrain(h, "batch", "model", None, tag="seqpar")   # sequence parallel
            hn = rms_norm(h, lp["norm"], cfg.norm_eps)
            y, (conv, state) = S.ssm_forward(lp["ssm"], hn, cfg.ssm,
                                             cfg.d_model, cfg.norm_eps)
            conv = tuple(c.astype(cdt) for c in conv)
            return h + y, (conv, state)
        convs, states, sks, svs = [], [], [], []
        for g in range(_num_shared_apps(cfg)):
            hn = rms_norm(x, params["shared_attn"]["attn_norm"], cfg.norm_eps)
            q, k, v = A.qkv_project(params["shared_attn"]["attn"], hn,
                                    positions, cfg.attn, cfg.norm_eps)
            a = A.flash_attention(q, k, v, causal=True, window=win)
            x = x + a.reshape(B, T, -1) @ params["shared_attn"]["attn"]["wo"]
            hn = rms_norm(x, params["shared_attn"]["mlp_norm"], cfg.norm_eps)
            x = x + mlp_apply(params["shared_attn"]["mlp"], hn, cfg.act)
            sks.append(_build_cache_slice(k, C, win).astype(cdt))
            svs.append(_build_cache_slice(v, C, win).astype(cdt))
            lo, hi = g * ae, min((g + 1) * ae, cfg.num_layers)
            gp = jax.tree_util.tree_map(lambda t: t[lo:hi], params["layers"])
            x, (conv, state) = jax.lax.scan(layer, x, gp)
            convs.append(conv)
            states.append(state)
        cache["conv_x"] = jnp.concatenate([c[0] for c in convs], axis=0)
        cache["conv_B"] = jnp.concatenate([c[1] for c in convs], axis=0)
        cache["conv_C"] = jnp.concatenate([c[2] for c in convs], axis=0)
        cache["state"] = jnp.concatenate(states, axis=0)
        cache["shared_k"] = jnp.stack(sks, axis=0)
        cache["shared_v"] = jnp.stack(svs, axis=0)
        aux = {}
    else:
        raise ValueError(cfg.family)

    x_last = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = lm_head_apply(cfg, params, x_last)[:, 0]
    return logits, cache, (aux if isinstance(aux, dict) else {})


# ---------------------------------------------------------------- decode --

def _ssm_decode_multi(lp, h: jnp.ndarray, conv, state, cfg: ArchConfig):
    """h: (B,T,d) -> (B,T,d), scanning the recurrence over T steps."""
    T = h.shape[1]
    if T == 1:
        y, (conv, state) = S.ssm_decode(lp, h[:, 0], (conv, state),
                                        cfg.ssm, cfg.d_model, cfg.norm_eps)
        return y[:, None], conv, state

    def step(c, xt):
        y, c2 = S.ssm_decode(lp, xt, c, cfg.ssm, cfg.d_model, cfg.norm_eps)
        return c2, y
    (conv, state), ys = jax.lax.scan(step, (conv, state),
                                     h.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), conv, state


def decode_step(cfg: ArchConfig, params, tokens: jnp.ndarray, cache: Dict, *,
                policy: XSharePolicy = OFF,
                spec_shape: Optional[Tuple[int, int]] = None,
                force_window: Optional[int] = None,
                capacity_factor: float = 2.0,
                active: Optional[jnp.ndarray] = None,
                dispatch: str = "auto",
                spec_priors: Optional[jnp.ndarray] = None):
    """Serve step: T new tokens per sequence (T=1 plain decode, T=1+L_s
    speculative verify). tokens: (B, T) (audio: (B,T,K)).

    active: optional (B,) bool — compute-mask for continuous batching:
    rows that are False (finished / empty slots) are excluded from MoE
    routing (no expert activation, no capacity consumption, no influence
    on XShare batch selection) and their aux metrics. Their logits are
    garbage the caller must ignore.

    spec_priors: optional (B, E) per-request gate-histogram priors for
    mode="spec" correlation-aware selection (see core/selection.py).

    Returns (logits (B,T,V[,K->(B,T,K,V)]), new cache, aux)."""
    x = embed_tokens(cfg, params, tokens)
    B, T = x.shape[:2]
    cur = jnp.asarray(cache["cur_len"])
    base = cur.reshape(-1, 1) if cur.ndim else jnp.full((B, 1), cur)
    positions = base + jnp.arange(T)[None, :]            # (B, T)
    win = effective_window(cfg, force_window=force_window)
    token_mask = None if active is None else \
        jnp.broadcast_to(active[:, None], (B, T))

    new_cache = dict(cache)
    if cfg.family in ("dense", "vlm", "audio", "moe"):
        # the stacked cache rides in the carry (updated in place), not in
        # xs/ys, which would allocate and fill a fresh stacked output
        def layer(carry, xs):
            h, kv_k, kv_v = carry
            lp, l = xs
            h, kv_k, kv_v = _attn_block_decode(cfg, lp, h, positions, kv_k,
                                               kv_v, l, cur, win)
            h, aux = _ffn_block(cfg, lp, h, policy, spec_shape, None,
                                capacity_factor, token_mask, dispatch,
                                spec_priors)
            return (h, kv_k, kv_v), aux
        (x, new_cache["kv_k"], new_cache["kv_v"]), aux = jax.lax.scan(
            layer, (x, cache["kv_k"], cache["kv_v"]),
            (params["layers"], jnp.arange(cfg.num_layers)))
    elif cfg.family == "ssm":
        def layer(h, xs):
            lp, conv, state = xs
            hn = rms_norm(h, lp["norm"], cfg.norm_eps)
            y, conv, state = _ssm_decode_multi(lp["ssm"], hn, conv, state,
                                               cfg)
            return h + y, (conv, state)
        x, (convs, states) = jax.lax.scan(
            layer, x, (params["layers"],
                       (cache["conv_x"], cache["conv_B"], cache["conv_C"]),
                       cache["state"]))
        (new_cache["conv_x"], new_cache["conv_B"],
         new_cache["conv_C"]) = convs
        new_cache["state"] = states
        aux = {}
    elif cfg.family == "hybrid":
        ae = cfg.attn_every
        def layer(h, xs):
            lp, conv, state = xs
            hn = rms_norm(h, lp["norm"], cfg.norm_eps)
            y, conv, state = _ssm_decode_multi(lp["ssm"], hn, conv, state,
                                               cfg)
            return h + y, (conv, state)
        convs, states, sks, svs = [], [], [], []
        for g in range(_num_shared_apps(cfg)):
            x, (sk, sv) = _shared_attn_block(
                cfg, params["shared_attn"], x, positions, win,
                cache=(cache["shared_k"][g], cache["shared_v"][g]),
                cur_len=cur)
            sks.append(sk)
            svs.append(sv)
            lo, hi = g * ae, min((g + 1) * ae, cfg.num_layers)
            gp = jax.tree_util.tree_map(lambda t: t[lo:hi], params["layers"])
            x, (conv, state) = jax.lax.scan(
                layer, x, (gp,
                           (cache["conv_x"][lo:hi], cache["conv_B"][lo:hi],
                            cache["conv_C"][lo:hi]),
                           cache["state"][lo:hi]))
            convs.append(conv)
            states.append(state)
        new_cache["conv_x"] = jnp.concatenate([c[0] for c in convs], axis=0)
        new_cache["conv_B"] = jnp.concatenate([c[1] for c in convs], axis=0)
        new_cache["conv_C"] = jnp.concatenate([c[2] for c in convs], axis=0)
        new_cache["state"] = jnp.concatenate(states, axis=0)
        new_cache["shared_k"] = jnp.stack(sks, axis=0)
        new_cache["shared_v"] = jnp.stack(svs, axis=0)
        aux = {}
    else:
        raise ValueError(cfg.family)

    new_cache["cur_len"] = cur + T
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_head_apply(cfg, params, x)
    return logits, new_cache, (aux if isinstance(aux, dict) else {})
