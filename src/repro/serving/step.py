"""Fused on-device decode step for the continuous-batching scheduler.

The seed engine ran one jitted decode_step per token with a host round
trip (argmax on host, np.asarray sync, python loop bookkeeping) between
steps. Here the whole inner loop moves on device: sampling happens
inside the jitted function (PRNG keys threaded through the scan) and
``decode_steps_fused`` advances N tokens per dispatch as a lax.scan, so
the host is touched once per N tokens — exactly the cadence at which the
scheduler intervenes (admission / eviction / harvest).

Per-slot active masks make the fixed-size running batch safe: finished /
empty slots are compute-masked out of MoE routing (decode_step's
``active`` arg — no expert activation, no dispatch capacity, no XShare
selection influence), their cur_len does not advance, and their emitted
tokens are garbage the scheduler never reads.

Numerics quarantine: the scan checks every slot's last-position logits
for non-finite values *before* sampling. A poisoned slot is frozen on
the spot — token held, cur_len not advanced, compute-masked out of
routing from the next step — and reported to the scheduler via the
returned ``poisoned`` mask, so one NaN terminates one request instead
of the whole fused batch. Detection is a pure elementwise pass over
logits already materialized; on a healthy batch every guard `where`
is the identity, keeping the fault-free path bit-exact.

build_step_fns() bundles every compiled function the scheduler needs;
jit retraces per input shape, so one bundle serves any batch size /
prompt length.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, XSharePolicy
from repro.core.selection import gate_histogram
from repro.models import decode_step, embed_tokens, prefill
from repro.models.layers import rms_norm
from repro.models.model import evict_slot, insert_request
from repro.models.moe import OFF
from repro.serving.sampler import sample_step
from repro.serving.spec_decode import greedy_accept

NO_FAULT = (-1, -1)   # disabled (slot, step) NaN-injection operand


def decode_steps_fused(cfg: ArchConfig, params, tok: jnp.ndarray,
                       cache: dict, remaining: jnp.ndarray, key, *,
                       num_steps: int,
                       policy: XSharePolicy = OFF,
                       temperature: float = 0.0,
                       force_window: Optional[int] = None,
                       capacity_factor: float = 8.0,
                       dispatch: str = "auto",
                       fault: Optional[jnp.ndarray] = None):
    """Run `num_steps` decode+sample steps as one on-device lax.scan.

    tok: (B,) int32 — each slot's last emitted token ((B, K) audio).
    remaining: (B,) int32 — tokens each slot still owes (0 = empty /
    evicted slot). The per-step active mask is `remaining > 0` and
    decrements on device, so a slot that reaches its budget MID-CHUNK
    deactivates on the very next step: its rows stop feeding XShare
    batch selection and the activation metrics, and its cache cur_len
    freezes. Evicted slots stay inert no matter how many scans pass
    before a new request is inserted over them.

    dispatch: MoE expert-compute path (models/moe.py) — the fused scan
    and the dense decode fast path unify behind this one switch
    ("auto": dense off-mesh at decode sizes, sorted grouped-GEMM
    dispatch elsewhere).

    fault: optional (2,) int32 (slot, step-in-chunk) — the fault-
    injection harness (serving/faults.py) poisons that slot's logits
    with NaN at that step. A *traced* operand, so fault campaigns and
    production runs share one compiled scan; (-1, -1) disables it.

    Returns (tok', cache', toks (num_steps, B[, K]), aux,
    ok (num_steps, B) bool, poisoned (B,) bool): `ok[i, b]` marks a
    real harvested token (slot active and finite at step i), `poisoned`
    flags slots quarantined for non-finite logits.
    """
    B = tok.shape[0]
    fault = jnp.asarray(NO_FAULT if fault is None else fault, jnp.int32)

    def body(carry, step_i):
        tok, cache, remaining, poisoned, key = carry
        active = (remaining > 0) & ~poisoned
        cur0 = cache["cur_len"]
        lg, cache, aux = decode_step(
            cfg, params, tok[:, None], cache, policy=policy,
            force_window=force_window, capacity_factor=capacity_factor,
            active=active, dispatch=dispatch)
        with jax.named_scope("sample"):
            last = lg[:, -1]                      # (B, V) or (B, K, V)
            inject = (jnp.arange(B) == fault[0]) & (step_i == fault[1])
            last = jnp.where(inject.reshape((B,) + (1,) * (last.ndim - 1)),
                             jnp.nan, last)
            finite = jnp.isfinite(last).reshape(B, -1).all(axis=1)
            ok = active & finite                  # (B,) harvestable step
            poisoned = poisoned | (active & ~finite)
            key, sub = jax.random.split(key)
            nxt = sample_step(last, sub, temperature=temperature)
            okm = ok if tok.ndim == 1 else ok[:, None]
            nxt = jnp.where(okm, nxt, tok)
            cache["cur_len"] = jnp.where(ok, cur0 + 1, cur0)
            remaining = remaining - ok.astype(remaining.dtype)
        return (nxt, cache, remaining, poisoned, key), (nxt, ok, aux)

    # modest unroll: fewer while-loop trips and better cross-step fusion
    # without blowing up compile time for large chunks
    carry0 = (tok, cache, remaining, jnp.zeros((B,), bool), key)
    (tok, cache, remaining, poisoned, key), (toks, oks, aux) = jax.lax.scan(
        body, carry0, jnp.arange(num_steps, dtype=jnp.int32),
        unroll=min(4, num_steps))
    return tok, cache, toks, aux, oks, poisoned


def spec_steps_fused(cfg: ArchConfig, params, dcfg: ArchConfig, dparams,
                     tok: jnp.ndarray, cache: dict, dcache: dict,
                     remaining: jnp.ndarray, budget: jnp.ndarray,
                     draft_len: jnp.ndarray, spec_on: jnp.ndarray,
                     priors: jnp.ndarray, *,
                     num_rounds: int, spec_len: int,
                     policy: XSharePolicy = OFF,
                     force_window: Optional[int] = None,
                     capacity_factor: float = 8.0,
                     dispatch: str = "auto",
                     fault: Optional[jnp.ndarray] = None):
    """Fused draft-then-verify: `num_rounds` speculative rounds as one
    on-device lax.scan, speculative and plain requests sharing one
    running batch.

    Each round drafts up to `spec_len` tokens per slot with the draft
    model (inner lax.scan of spec_len+1 steps — the extra step writes
    the last draft's KV, mirroring the lockstep reference), then runs
    ONE target verify pass over (B, 1+spec_len) tokens — the paper's
    amplified batch shape — routed with XSharePolicy(mode="spec") and
    the scheduler's per-slot correlation priors. Ragged acceptance
    (greedy_accept with a per-slot `limit`) rolls both caches back to
    cur0 + num_new, so draft and target cur_len stay equal for every
    speculative slot.

    Heterogeneous batches fall out of the per-slot limit
    ``lim = min(draft_len, remaining-1, budget)`` (zeroed for inactive
    or non-speculative slots): a slot with lim == 0 degenerates exactly
    to plain greedy decode — accepted 0, one bonus token from the
    verify pass's position-0 logits — so plain requests ride the same
    dispatch. Speculative slots with an exhausted budget keep drafting
    through the draft scan (dactive) so their draft cache stays in
    lockstep with the target cache, but accept nothing (lim == 0).

    tok: (B,) each slot's last emitted (uncached) token.
    remaining: (B,) tokens still owed (0 = empty slot).
    budget: (B,) draft tokens each slot may still spend.
    draft_len: (B,) per-slot adaptive draft length, <= spec_len.
    spec_on: (B,) bool — slot runs the draft model.
    priors: (B, E) gate-histogram correlation priors ((B, 0) when the
    target has no router).
    fault: optional (2,) int32 (slot, round-in-chunk) NaN injection into
    that round's verify logits, as in decode_steps_fused.

    Returns (tok', cache', dcache', remaining', budget',
    new_tokens (R, B, spec_len+1), num_new (R, B), accepted (R, B),
    drafted (R, B), aux, poisoned (B,)): harvest row r of slot b with
    ``new_tokens[r, b, :num_new[r, b]]``. num_new never exceeds the
    slot's remaining budget (the -1 in lim reserves room for the bonus
    token), so harvested tokens need no overshoot trimming.
    """
    B = tok.shape[0]
    fault = jnp.asarray(NO_FAULT if fault is None else fault, jnp.int32)
    use_priors = priors.shape[-1] > 0

    def round_body(carry, round_i):
        tok, cache, dcache, remaining, budget, poisoned = carry
        active = (remaining > 0) & ~poisoned
        dactive = active & spec_on
        lim = jnp.minimum(jnp.minimum(draft_len,
                                      jnp.maximum(remaining - 1, 0)),
                          budget)
        lim = jnp.where(dactive, lim, 0).astype(jnp.int32)

        # -- draft spec_len tokens (one extra step writes the last KV) --
        def draft_body(c, _):
            dtok, dcache = c
            dcur0 = dcache["cur_len"]
            dlg, dcache, _ = decode_step(
                dcfg, dparams, dtok[:, None], dcache,
                capacity_factor=capacity_factor, active=dactive,
                dispatch=dispatch)
            nxt = jnp.argmax(dlg[:, -1], axis=-1).astype(jnp.int32)
            nxt = jnp.where(dactive, nxt, dtok)
            dcache["cur_len"] = jnp.where(dactive, dcur0 + 1, dcur0)
            return (nxt, dcache), nxt

        dstart = dcache["cur_len"]
        (_, dcache), douts = jax.lax.scan(
            draft_body, (tok, dcache), None, length=spec_len + 1)
        drafts = douts[:spec_len].T                     # (B, spec_len)

        # -- single verify pass over (B, 1+spec_len) ---------------------
        verify_in = jnp.concatenate([tok[:, None], drafts], axis=1)
        cur0 = cache["cur_len"]
        vlg, cache, aux = decode_step(
            cfg, params, verify_in, cache, policy=policy,
            spec_shape=(B, 1 + spec_len), force_window=force_window,
            capacity_factor=capacity_factor, active=active,
            dispatch=dispatch,
            spec_priors=(priors * active[:, None] if use_priors else None))
        inject = (jnp.arange(B) == fault[0]) & (round_i == fault[1])
        vlg = jnp.where(inject[:, None, None], jnp.nan, vlg)
        finite = jnp.isfinite(vlg).reshape(B, -1).all(axis=1)
        ok = active & finite
        poisoned = poisoned | (active & ~finite)

        res = greedy_accept(vlg, drafts, limit=lim)
        num_new = jnp.where(ok, res.num_new, 0).astype(jnp.int32)
        # ragged rollback: both caches advance by this round's emission;
        # verify KV written above cur0+num_new is dead and overwritten
        # by later rounds (same as inactive rows on the plain path)
        cache["cur_len"] = cur0 + num_new
        dcache["cur_len"] = jnp.where(dactive, dstart + num_new,
                                      dcache["cur_len"])
        x0 = jnp.take_along_axis(res.new_tokens, res.accepted[:, None],
                                 axis=1)[:, 0]
        tok = jnp.where(ok, x0, tok)
        remaining = remaining - num_new
        budget = budget - jnp.where(dactive, lim, 0)
        outs = (res.new_tokens, num_new, res.accepted.astype(jnp.int32),
                lim, aux)
        return (tok, cache, dcache, remaining, budget, poisoned), outs

    carry0 = (tok, cache, dcache, remaining, budget, jnp.zeros((B,), bool))
    (tok, cache, dcache, remaining, budget, poisoned), \
        (new_tokens, num_new, accepted, drafted, aux) = jax.lax.scan(
            round_body, carry0, jnp.arange(num_rounds, dtype=jnp.int32))
    return (tok, cache, dcache, remaining, budget,
            new_tokens, num_new, accepted, drafted, aux, poisoned)


def gate_probe(cfg: ArchConfig, params, tokens: jnp.ndarray) -> jnp.ndarray:
    """Cheap router probe: a request's expert gate histogram (E,).

    Embeds the prompt and runs only the *first MoE layer's* router on the
    (pre-attention) hidden states — no attention, no FFN, no cache — so
    the scheduler can score a waiting request's expert affinity without
    paying for a prefill. An approximation of the true decode-time gate
    histogram, but the domain signal the admission policy needs (which
    experts a request leans on) is already present at the embedding.
    """
    x = embed_tokens(cfg, params, tokens)              # (B, S, d)
    h = rms_norm(x, params["layers"]["moe_norm"][0], cfg.norm_eps)
    wg = jnp.asarray(params["layers"]["moe"]["wg"][0], jnp.float32)
    probs = jax.nn.softmax(jnp.asarray(h, jnp.float32) @ wg, axis=-1)
    return gate_histogram(probs).mean(axis=0)          # (E,)


@dataclass
class StepFns:
    """Compiled serving functions shared by Engine and Scheduler. fused,
    insert, evict and evict_scrub donate the running cache they take:
    use the cache they return, never the one passed in."""
    prefill: Callable        # (params, tokens)            -> (lg, cache, aux)
    fused: Callable          # (params, tok, cache, remaining, key, fault)
    #                        -> (tok', cache', toks, aux, ok, poisoned)
    insert: Callable         # (cache, req_cache, slot)    -> cache
    evict: Callable          # (cache, slot)               -> cache
    evict_scrub: Callable    # (cache, slot) -> cache, row zeroed (poisoned)
    probe: Optional[Callable]  # (params, tokens) -> (E,) | None (no MoE)
    decode_chunk: int


def make_fused(cfg: ArchConfig, *,
               policy: XSharePolicy = OFF,
               decode_chunk: int = 8,
               temperature: float = 0.0,
               force_window: Optional[int] = None,
               capacity_factor: float = 8.0,
               dispatch: str = "auto") -> Callable:
    """One jitted fused-scan closure. Split out of build_step_fns so the
    scheduler's graceful-degradation ladder can compile variants with a
    tightened XShare policy while sharing the rest of the bundle. The
    cache (argument 2) is donated: the call updates it in place."""
    return jax.jit(lambda p, tok, c, rem, key, fault: decode_steps_fused(
        cfg, p, tok, c, rem, key, num_steps=decode_chunk, policy=policy,
        temperature=temperature, force_window=force_window,
        capacity_factor=capacity_factor, dispatch=dispatch, fault=fault),
        donate_argnums=2)


def build_step_fns(cfg: ArchConfig, *,
                   policy: XSharePolicy = OFF,
                   cache_len: int = 512,
                   decode_chunk: int = 8,
                   temperature: float = 0.0,
                   force_window: Optional[int] = None,
                   capacity_factor: float = 8.0,
                   dispatch: str = "auto") -> StepFns:
    """Build the jitted function bundle for one (model config, serving
    config) pair. decode_chunk is the N of decode_steps_fused — the
    number of tokens generated between scheduler interventions."""
    pre = jax.jit(lambda p, t: prefill(
        cfg, p, t, cache_len=cache_len, policy=OFF,
        force_window=force_window, capacity_factor=capacity_factor,
        dispatch=dispatch))
    fused = make_fused(cfg, policy=policy, decode_chunk=decode_chunk,
                       temperature=temperature, force_window=force_window,
                       capacity_factor=capacity_factor, dispatch=dispatch)
    probe = None
    if cfg.family == "moe":
        probe = jax.jit(lambda p, t: gate_probe(cfg, p, t))
    # insert and evict donate the running cache (argument 0), like fused
    return StepFns(prefill=pre, fused=fused,
                   insert=jax.jit(insert_request, donate_argnums=0),
                   evict=jax.jit(evict_slot, donate_argnums=0),
                   evict_scrub=jax.jit(
                       lambda c, s: evict_slot(c, s, scrub=True),
                       donate_argnums=0),
                   probe=probe, decode_chunk=decode_chunk)


# ------------------------------------------------- speculative bundle ----

@dataclass
class SpecStepFns:
    """Compiled speculative-decoding functions layered on top of a
    StepFns bundle (serving/spec_scheduler.py drives both)."""
    dprefill: Callable   # (dparams, tokens) -> (lg, dcache, aux)
    fused: Callable      # (p, dp, tok, cache, dcache, remaining, budget,
    #                       draft_len, spec_on, priors, fault) -> 11-tuple
    spec_len: int        # max draft tokens per round (static)
    num_rounds: int      # draft-verify rounds per dispatch (static)


def make_spec_fused(cfg: ArchConfig, dcfg: ArchConfig, *,
                    policy: XSharePolicy = OFF,
                    spec_len: int,
                    num_rounds: int,
                    force_window: Optional[int] = None,
                    capacity_factor: float = 8.0,
                    dispatch: str = "auto") -> Callable:
    """One jitted fused spec-scan closure (split out, like make_fused,
    so the degradation ladder can compile tightened-policy variants)."""
    return jax.jit(lambda p, dp, tok, c, dc, rem, bud, dl, so, pri, fault:
                   spec_steps_fused(
                       cfg, p, dcfg, dp, tok, c, dc, rem, bud, dl, so, pri,
                       num_rounds=num_rounds, spec_len=spec_len,
                       policy=policy, force_window=force_window,
                       capacity_factor=capacity_factor, dispatch=dispatch,
                       fault=fault))


def build_spec_fns(cfg: ArchConfig, dcfg: ArchConfig, *,
                   policy: XSharePolicy = OFF,
                   spec_len: int,
                   num_rounds: int = 4,
                   cache_len: int = 512,
                   force_window: Optional[int] = None,
                   capacity_factor: float = 8.0,
                   dispatch: str = "auto") -> SpecStepFns:
    """Speculative bundle for one (target, draft) model pair. `policy`
    must already be spec-compatible (mode "off" or "spec" — the Engine
    maps other modes to OFF for the verify pass, mirroring _verify)."""
    dpre = jax.jit(lambda p, t: prefill(
        dcfg, p, t, cache_len=cache_len,
        capacity_factor=capacity_factor))
    fused = make_spec_fused(cfg, dcfg, policy=policy, spec_len=spec_len,
                            num_rounds=num_rounds,
                            force_window=force_window,
                            capacity_factor=capacity_factor,
                            dispatch=dispatch)
    return SpecStepFns(dprefill=dpre, fused=fused, spec_len=spec_len,
                       num_rounds=num_rounds)
