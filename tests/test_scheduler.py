"""Continuous-batching serving subsystem (scheduler + fused decode step).

The load-bearing invariants:
  * lockstep equivalence — continuous batching with simultaneous
    arrivals reproduces the per-token host loop's tokens exactly;
  * cache integrity — a request admitted or evicted mid-stream decodes
    exactly as if it had the machine to itself (insert/evict surgery and
    the per-slot active mask never leak across slots);
  * fused-scan parity — N-token lax.scan decode == per-step decode.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.models import init_params
from repro.models.model import evict_slot, init_cache, insert_request
from repro.serving import Engine, Scheduler
from repro.serving.step import NO_FAULT, decode_steps_fused


def small(name, **kw):
    return ARCHS[name].reduced(num_layers=2, max_d_model=128,
                               max_vocab=256, **kw)


@pytest.fixture(scope="module")
def moe_setup():
    cfg = small("granite-moe-1b-a400m")
    params = init_params(cfg, jax.random.PRNGKey(1))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (3, 12), 0, cfg.vocab_size))
    return cfg, params, prompts


# ------------------------------------------------- lockstep equivalence ---

def test_continuous_matches_lockstep_t0(moe_setup):
    """All requests at t=0 => token-exact vs. the seed per-token loop."""
    cfg, params, prompts = moe_setup
    eng = Engine(cfg, params, cache_len=128, decode_chunk=4)
    lock, st_l = eng.generate(prompts, 20, lockstep=True)
    cont, st_c = eng.generate(prompts, 20)
    assert np.array_equal(lock, cont)
    assert st_c.new_tokens == st_l.new_tokens
    assert st_c.layer_aux, "continuous path must keep XShare aux metrics"


def test_continuous_matches_lockstep_dense_window():
    """Rolling-window cache survives insert_request surgery."""
    cfg = small("h2o-danube-1.8b")
    assert cfg.attn.sliding_window
    params = init_params(cfg, jax.random.PRNGKey(3))
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (2, 10), 0, cfg.vocab_size))
    eng = Engine(cfg, params, cache_len=128, decode_chunk=8)
    lock, _ = eng.generate(prompts, 30, lockstep=True)
    cont, _ = eng.generate(prompts, 30)
    assert np.array_equal(lock, cont)


# ----------------------------------------- mid-stream admission/eviction --

def test_midstream_admission_cache_integrity(moe_setup):
    """num_slots < num_requests: later requests are admitted into slots
    vacated mid-stream (different max_new per request staggers
    completions). Every request must decode exactly as it does alone —
    any cross-slot cache leak or active-mask bug breaks this."""
    cfg, params, _ = moe_setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
               for s in (12, 12, 9, 15)]
    lens = [6, 14, 10, 8]

    eng = Engine(cfg, params, cache_len=128, decode_chunk=3)
    solo = [eng.generate(p[None], n)[0][0] for p, n in zip(prompts, lens)]

    sched = eng.make_scheduler(num_slots=2)
    for p, n in zip(prompts, lens):
        sched.submit(p, n)
    states = sched.run()
    assert all(s.status == "done" for s in states)
    for st, ref in zip(states, solo):
        assert np.array_equal(np.stack(st.tokens), ref), st.req.rid


def test_insert_evict_roundtrip(moe_setup):
    """Cache surgery unit: inserted row matches the prefilled source row;
    evict only zeroes that slot's cur_len."""
    cfg, params, prompts = moe_setup
    eng = Engine(cfg, params, cache_len=64)
    _, req_cache, _ = eng._prefill(params, prompts[1:2])
    batch = init_cache(cfg, 3, 64, jnp.float32)
    batch = insert_request(batch, req_cache, 2)
    assert np.asarray(batch["cur_len"]).tolist() == [0, 0, 12]
    np.testing.assert_array_equal(np.asarray(batch["kv_k"][:, 2]),
                                  np.asarray(req_cache["kv_k"][:, 0]))
    assert not np.asarray(batch["kv_k"][:, 0]).any()
    batch = evict_slot(batch, 2)
    assert np.asarray(batch["cur_len"]).tolist() == [0, 0, 0]


@pytest.mark.parametrize("call", ["fused", "insert", "evict",
                                  "evict_scrub"])
def test_step_calls_consume_the_running_cache(moe_setup, call):
    """The bundle's calls that take the running cache update it in place:
    the cache handed in is deleted (donated), and what comes back equals
    the same function's result undonated, on a copy."""
    cfg, params, prompts = moe_setup
    eng = Engine(cfg, params, cache_len=64, decode_chunk=2)
    fns = eng._fns
    _, req_cache, _ = eng._prefill(params, prompts[:2])
    cache = insert_request(init_cache(cfg, 3, 64, jnp.float32), req_cache, 1)
    keep = jax.tree.map(jnp.copy, cache)
    tok = jnp.asarray([0, 7, 0], jnp.int32)
    rem = jnp.asarray([0, 2, 0], jnp.int32)
    key, fault = jax.random.PRNGKey(0), jnp.asarray(NO_FAULT, jnp.int32)
    slot, src = jnp.asarray(1, jnp.int32), jnp.asarray(1, jnp.int32)
    donated, plain = {
        "fused": (lambda c: fns.fused(params, tok, c, rem, key, fault)[1],
                  lambda c: decode_steps_fused(
                      cfg, params, tok, c, rem, key, num_steps=2,
                      fault=fault)[1]),
        "insert": (lambda c: fns.insert(c, req_cache, slot, src),
                   lambda c: insert_request(c, req_cache, slot, src)),
        "evict": (lambda c: fns.evict(c, slot),
                  lambda c: evict_slot(c, slot)),
        "evict_scrub": (lambda c: fns.evict_scrub(c, slot),
                        lambda c: evict_slot(c, slot, scrub=True)),
    }[call]
    out = donated(cache)
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))
    ref = jax.jit(plain)(keep)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]),
                                      err_msg=k)


# ------------------------------------------------------ fused-scan parity --

def test_fused_chunk_size_invariance(moe_setup):
    """decode_steps_fused is a pure refactor of the per-step loop: the
    emitted tokens cannot depend on the scan chunk size."""
    cfg, params, prompts = moe_setup
    outs = []
    for chunk in (1, 5):
        eng = Engine(cfg, params, cache_len=128, decode_chunk=chunk)
        toks, _ = eng.generate(prompts, 17)
        outs.append(toks)
    assert np.array_equal(outs[0], outs[1])


def test_fused_masks_inactive_slots(moe_setup):
    """A partially-empty running batch must route exactly like the
    occupied rows alone: inactive slots are compute-masked out of MoE
    selection and the activation statistics, so the 4-slot/3-request
    run's per-step activated-expert counts equal the 3-slot run's."""
    cfg, params, prompts = moe_setup
    eng = Engine(cfg, params, cache_len=128, decode_chunk=4)
    acts = []
    for slots in (4, 3):                 # 3 requests either way
        sched = eng.make_scheduler(num_slots=slots)
        for b in range(prompts.shape[0]):
            sched.submit(prompts[b], 10)
        states = sched.run()
        lock, _ = eng.generate(prompts, 10, lockstep=True)
        for b, st in enumerate(states):
            assert np.array_equal(np.stack(st.tokens), lock[b])
        acts.append(np.array([np.asarray(a["activated_experts"])
                              for a in sched.step_aux]))
    np.testing.assert_array_equal(acts[0], acts[1])


# -------------------------------------------------- affinity admission ----

def test_affinity_admission_orders_by_overlap(moe_setup):
    """With a running batch in place, affinity admission pops the queued
    request with the most similar gate histogram, not the FIFO head."""
    cfg, params, prompts = moe_setup
    eng = Engine(cfg, params, cache_len=128, decode_chunk=2)
    sched = eng.make_scheduler(num_slots=2, admission="affinity")
    for b in range(prompts.shape[0]):
        sched.submit(prompts[b], 8)
    states = sched.run()
    assert all(s.status == "done" for s in states)
    assert all(s.gate_hist is not None and s.gate_hist.shape ==
               (cfg.moe.num_experts,) for s in states)
    # affinity scheduling must not corrupt decoding
    for st in states:
        solo, _ = eng.generate(st.req.prompt[None], 8)
        assert np.array_equal(np.stack(st.tokens), solo[0])


def test_gate_priors_stable_api(moe_setup):
    """Scheduler.gate_priors() — the stable per-slot expert-affinity
    read API (EP placement, affinity admission): (num_slots, E), rows
    mirror occupied slots' gate histograms, zeros elsewhere."""
    cfg, params, prompts = moe_setup
    eng = Engine(cfg, params, cache_len=128, decode_chunk=2)
    E = cfg.moe.num_experts
    captured = []
    sched = eng.make_scheduler(
        num_slots=2, admission="affinity",
        on_round=lambda s, r: captured.append(s.gate_priors()))
    for b in range(prompts.shape[0]):
        sched.submit(prompts[b], 6)
    # empty batch: correct shape, all zero
    assert sched.gate_priors().shape == (2, E)
    assert not sched.gate_priors().any()
    states = sched.run()
    assert all(s.status == "done" for s in states)
    assert captured
    for pri in captured:
        assert pri.shape == (2, E)
        assert np.isfinite(pri).all() and (pri >= 0).all()
    # a full batch mid-run carries a prior per occupied slot
    full = max(captured, key=lambda p: (p.sum(1) > 0).sum())
    assert (full.sum(1) > 0).all()
    # rows are the admission-time histograms the affinity path uses
    rows = {tuple(np.round(s.gate_hist, 12)) for s in states}
    for r in range(2):
        assert tuple(np.round(full[r], 12)) in rows


def test_scheduler_latency_accounting(moe_setup):
    cfg, params, prompts = moe_setup
    eng = Engine(cfg, params, cache_len=128, decode_chunk=2)
    sched = eng.make_scheduler(num_slots=3)
    for b in range(prompts.shape[0]):
        sched.submit(prompts[b], 6)
    states = sched.run()
    for st in states:
        assert 0.0 <= st.ttft_s <= st.latency_s
        assert len(st.tokens) == 6
        assert len(st.layer_aux) == 5    # tokens after the prefill token
