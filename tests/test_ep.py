"""Expert-parallel execution: the shard_map EP path must agree with the
single-device sorted pipeline within ep.EP_TOL_EPS (the cases below
that compare bit for bit round alike on the CPU backend; the top_k=8
case does not, and a planted lost or misrouted row must fail the
check), which is itself checked
against the einsum reference and the dense oracle), across top_k,
ragged skewed loads, masked continuous-batching tokens, replicated hot
experts, and XShare-restricted routing — plus unit coverage of the
histogram-driven placement planner (LPT assignment, deterministic
tie-breaks, replication, rebalance hysteresis).

conftest.py forces an 8-device emulated CPU platform, so the ragged
all-to-all here exchanges rows between real XLA devices in every
tier-1 run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ep as EP
from repro.configs.base import MoEConfig, XSharePolicy
from repro.models import dispatch as DSP
from repro.models.moe import expert_ffn, init_moe, route
from repro.sharding import make_ep_mesh

S = 8          # EP shards (== emulated device count)
D = 16         # d_model
E = 16         # experts
F = 32         # d_ff


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < S:
        pytest.skip(f"needs {S} devices (conftest XLA_FLAGS forcing)")
    return make_ep_mesh(S)


@pytest.fixture(scope="module")
def weights():
    moe = MoEConfig(num_experts=E, top_k=2, d_ff_expert=F)
    return moe, init_moe(jax.random.PRNGKey(0), moe, D, jnp.float32)


@pytest.fixture(scope="module")
def exec_contig(mesh):
    # one executor for the whole module: compiled shard_map variants
    # are cached per shape, so tests sharing (T, k) share compiles
    return EP.EPExecutor(mesh, EP.contiguous_placement(E, S))


def routing(T, k, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    # distinct experts per token (real top-k semantics — the einsum
    # reference's one-hot dispatch assumes no within-row duplicates)
    idx = np.stack([rng.permutation(E)[:k] for _ in range(T)])
    w = jnp.asarray(rng.random((T, k)) + 0.1, jnp.float32)
    return x, jnp.asarray(idx, jnp.int32), w


def sorted_ref(p, x, idx, w):
    return DSP.sorted_expert_ffn(x, p["w1"], p["w3"], p["w2"], idx, w)


# ------------------------------------------------- three-way parity -------

@pytest.mark.parametrize("k", [1, 2, 8])
def test_ep_sorted_einsum_three_way(mesh, weights, exec_contig, k):
    """shard_map EP == single-device sorted (within EP_TOL_EPS) ==
    einsum reference (float tolerance) for top_k in {1, 2, 8}."""
    moe, p = weights
    T = 40
    x, idx, w = routing(T, k, seed=k)
    y_sorted = sorted_ref(p, x, idx, w)
    y_ep, stats = exec_contig(x, p["w1"], p["w3"], p["w2"], idx, w)
    assert EP.ep_mismatch(y_ep, y_sorted) <= EP.EP_TOL_EPS
    assert stats.count_matrix.sum() == T * k
    y_einsum = expert_ffn(p, x, idx, w, moe, capacity=T, dispatch="einsum",
                          group_size=10 ** 9)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_einsum),
                               atol=1e-4)


@pytest.mark.parametrize("fault", ["drop", "swap"])
def test_ep_mismatch_flags_planted_fault(mesh, weights, exec_contig, fault):
    """The EP_TOL_EPS check fails when one (token, expert) row is lost
    or sent to the wrong expert: the smallest-gate pair of one token, so
    the fault is the least visible one in the batch."""
    moe, p = weights
    T, k = 40, 8
    x, idx, w = routing(T, k, seed=k)
    ref = sorted_ref(p, x, idx, w)
    t, j = np.unravel_index(int(np.argmin(np.asarray(w))), w.shape)
    if fault == "drop":
        w = w.at[t, j].set(0.0)
    else:
        spare = next(e for e in range(E) if e not in np.asarray(idx[t]))
        idx = idx.at[t, j].set(spare)
    y_ep, _ = exec_contig(x, p["w1"], p["w3"], p["w2"], idx, w)
    assert EP.ep_mismatch(y_ep, ref) > EP.EP_TOL_EPS


def test_ep_ragged_skew_and_token_mask(mesh, weights, exec_contig):
    """Heavily skewed expert loads + masked continuous-batching slots
    (idx == -1, w == 0): masked tokens ship no rows and the output
    stays exact. T not divisible by S exercises the pad path."""
    moe, p = weights
    T, k = 43, 2
    x, idx, w = routing(T, k, seed=7)
    idx = idx.at[: T // 2].set(3)             # most pairs on one expert
    idx = idx.at[5:9].set(-1)                 # inactive slots
    w = w.at[5:9].set(0.0)
    w = w.at[12, 1].set(0.0)                  # single dead pair
    y_ep, stats = exec_contig(x, p["w1"], p["w3"], p["w2"], idx, w)
    assert np.array_equal(np.asarray(y_ep),
                          np.asarray(sorted_ref(p, x, idx, w)))
    live = int(((np.asarray(idx).reshape(-1) >= 0)
                & (np.asarray(w).reshape(-1) != 0)).sum())
    assert stats.count_matrix.sum() == live
    # expert 3 lives on one shard under contiguous placement: that
    # shard's computed rows must dominate
    assert stats.peak_rows >= live // 2


def test_ep_replicated_hot_expert(mesh, weights):
    """Replicating the hottest expert splits its rows across replicas
    (token-id modulus) and cuts the measured peak, exactly."""
    moe, p = weights
    T, k = 40, 2
    x, idx, w = routing(T, k, seed=11)
    idx = jnp.zeros_like(idx)                 # every pair -> expert 0
    load = np.zeros(E)
    load[0] = T * k
    ex_plain = EP.EPExecutor(mesh, EP.plan_placement(load, S))
    ex_rep = EP.EPExecutor(
        mesh, EP.plan_placement(load, S, replicate_hot=1, max_replicas=4))
    y_plain, st_plain = ex_plain(x, p["w1"], p["w3"], p["w2"], idx, w)
    y_rep, st_rep = ex_rep(x, p["w1"], p["w3"], p["w2"], idx, w)
    ref = sorted_ref(p, x, idx, w)
    assert np.array_equal(np.asarray(y_plain), np.asarray(ref))
    assert np.array_equal(np.asarray(y_rep), np.asarray(ref))
    assert st_plain.peak_rows == T * k        # one shard eats everything
    assert st_rep.peak_rows <= -(-T * k // 4) + S   # ~1/4 per replica
    assert st_rep.count_matrix.sum() == T * k


def test_ep_xshare_restricted_routing(mesh, weights, exec_contig):
    """Routing through the real router under an XShare ep-mode policy
    (Algorithm 6 per-group budgets) stays exact end to end."""
    moe, p = weights
    T = 40
    x, _, _ = routing(T, moe.top_k, seed=3)
    policy = XSharePolicy(mode="ep", k0=1, m_g=1, num_groups=8)
    idx, w, _, _ = route(p, x, moe, policy)
    y_ep, _ = exec_contig(x, p["w1"], p["w3"], p["w2"], idx, w)
    assert np.array_equal(np.asarray(y_ep),
                          np.asarray(sorted_ref(p, x, idx, w)))


def test_ep_auto_max_rows(mesh, weights, exec_contig):
    """max_rows="auto" (counts exchanged first, payload padded to the
    pow2-bucketed per-round max) shrinks the exchange buffer and still
    matches the worst-case-padded result bit for bit."""
    moe, p = weights
    T, k = 40, 2
    x, idx, w = routing(T, k, seed=2)
    y_full, st_full = exec_contig(x, p["w1"], p["w3"], p["w2"], idx, w)
    y_auto, st_auto = exec_contig(x, p["w1"], p["w3"], p["w2"], idx, w,
                                  max_rows="auto")
    assert st_auto.max_rows < st_full.max_rows
    assert np.array_equal(np.asarray(y_auto), np.asarray(y_full))


def test_ep_dispatch_mode(mesh, weights, exec_contig):
    """expert_ffn(dispatch="ep") routes through the bound executor and
    refuses to run when none is bound (no silent single-device path)."""
    moe, p = weights
    T, k = 40, 2
    x, idx, w = routing(T, k, seed=5)
    y_sorted = expert_ffn(p, x, idx, w, moe, dispatch="sorted")
    with pytest.raises(ValueError, match="EPExecutor"):
        expert_ffn(p, x, idx, w, moe, dispatch="ep")
    with EP.ep_context(exec_contig):
        y_bound = expert_ffn(p, x, idx, w, moe, dispatch="ep")
    assert EP.current_executor() is None
    assert np.array_equal(np.asarray(y_bound), np.asarray(y_sorted))


def test_exchange_counts_matches_stats(mesh, weights, exec_contig):
    moe, p = weights
    x, idx, w = routing(40, 2, seed=9)
    cm = EP.exchange_counts(idx, w, exec_contig.placement, mesh=mesh)
    _, stats = exec_contig(x, p["w1"], p["w3"], p["w2"], idx, w)
    assert np.array_equal(cm, stats.count_matrix)


# ------------------------------------------------- placement planner ------

def skewed_load(E_, seed=0, alpha=1.2):
    rng = np.random.default_rng(seed)
    return np.sort(rng.pareto(alpha, E_) + 0.1)[::-1].copy()


def test_lpt_no_worse_than_contiguous():
    for seed in range(5):
        load = skewed_load(E, seed)
        lpt = EP.plan_placement(load, S)
        contig = EP.contiguous_placement(E, S)
        assert EP.placement_peak(lpt, load) <= \
            EP.placement_peak(contig, load)


def test_placement_deterministic_ties():
    load = np.ones(E)                         # every assignment tied
    a = EP.plan_placement(load, S, replicate_hot=2)
    b = EP.plan_placement(load, S, replicate_hot=2)
    assert np.array_equal(a.hosts, b.hosts)
    assert np.array_equal(a.local_eids, b.local_eids)
    assert np.array_equal(a.local_slot, b.local_slot)


def test_replication_reduces_predicted_peak():
    load = np.ones(E)
    load[0] = 100.0
    base = EP.plan_placement(load, S)
    rep = EP.plan_placement(load, S, replicate_hot=1, max_replicas=4)
    assert EP.placement_peak(rep, load) < EP.placement_peak(base, load)
    assert rep.nhosts[0] == 4
    assert rep.replication_factor > 1.0


def test_placement_tables_roundtrip():
    load = skewed_load(E, 3)
    pl = EP.plan_placement(load, S, replicate_hot=3, max_replicas=3)
    for e in range(E):
        for r in range(pl.nhosts[e]):
            s = pl.hosts[e, r]
            slot = pl.local_slot[s, e]
            assert slot >= 0
            assert pl.local_eids[s, slot] == e


def test_rebalance_hysteresis():
    load = np.ones(E)
    load[0] = 100.0
    # contiguous start vs a hot expert: big predicted win -> adopted
    prev = EP.contiguous_placement(E, S)
    new, changed = EP.rebalance(prev, load, replicate_hot=1,
                                max_replicas=4, hysteresis=0.1)
    assert changed and new.version == prev.version + 1
    # same load again: no further win -> hysteresis keeps the placement
    again, changed2 = EP.rebalance(new, load, replicate_hot=1,
                                   max_replicas=4, hysteresis=0.1)
    assert not changed2 and again is new


def test_executor_update_placement(mesh):
    load = np.ones(E)
    load[0] = 100.0
    ex = EP.EPExecutor(mesh, EP.contiguous_placement(E, S),
                       replicate_hot=1, max_replicas=4)
    assert ex.update_placement(load)
    assert ex.rebalances == 1
    assert not ex.update_placement(load)
    assert ex.rebalances_skipped == 1


def test_executor_from_config(mesh, weights):
    """EPConfig -> executor wiring: knobs land, priors shape the initial
    placement, and the configured path stays exact."""
    from repro.configs.base import EPConfig
    cfg = EPConfig(num_shards=S, replicate_hot=1, max_replicas=2,
                   rebalance_hysteresis=0.25)
    load = np.ones(E)
    load[3] = 50.0
    ex = EP.EPExecutor.from_config(cfg, E, mesh=mesh, load=load)
    assert ex.hysteresis == 0.25
    assert ex.placement.nhosts[3] == 2          # hottest got replicated
    _, p = weights
    x, idx, w = routing(24, 2, seed=9)
    y, _ = ex(x, p["w1"], p["w3"], p["w2"], idx, w)
    assert np.array_equal(np.asarray(y),
                          np.asarray(sorted_ref(p, x, idx, w)))
    # no mesh given: builds its own over the same 8 devices
    ex2 = EP.EPExecutor.from_config(EPConfig(num_shards=S), E)
    assert ex2.placement.num_shards == S


# ------------------------------------- group math, E % G != 0 (fix) -------

def test_group_loads_non_divisible():
    """E=6 over G=4 groups: ceil-width groups [2,2,2,0] — the old code
    collapsed to a single group and misreported shard load."""
    counts = jnp.asarray([1, 2, 3, 4, 5, 6], jnp.int32)
    loads = np.asarray(DSP.group_token_loads(counts, 4))
    assert loads.tolist() == [3, 7, 11, 0]
    from repro.core.metrics import max_group_load, per_group_load
    active = jnp.asarray([1, 0, 1, 1, 0, 1], bool)
    assert np.asarray(per_group_load(active, 4)).tolist() == [1, 2, 1, 0]
    assert int(max_group_load(active, 4)) == 2


def test_ep_select_non_divisible_groups():
    """Algorithm 6 selection with E % G != 0 keeps per-group budgets on
    the ceil-width partition (padding can never be selected)."""
    from repro.core.selection import ep_select
    rng = np.random.default_rng(0)
    gates = jnp.asarray(rng.random((12, 6)), jnp.float32)
    mask = np.asarray(ep_select(gates, 1, 4, 0, strict_cap=True))
    assert mask.shape == (6,)
    loads = np.asarray(DSP.group_token_loads(
        jnp.asarray(mask, jnp.int32), 4))
    assert (loads <= 1).all()


def test_dispatch_plan_pad_shards():
    """pad_shards keeps the sorted tile axis divisible by the shard
    count (outer-mesh layouts) and pad_shards=1 opts the EP executor's
    per-shard plans out of the ambient-mesh padding."""
    idx = jnp.asarray([[0], [1], [2]], jnp.int32)
    w = jnp.ones((3, 1), jnp.float32)
    plan = DSP.dispatch_plan(idx, w, 4, block_t=8, pad_shards=8)
    assert plan.padded_rows % (8 * 8) == 0
    plan1 = DSP.dispatch_plan(idx, w, 4, block_t=8, pad_shards=1)
    assert plan1.padded_rows < plan.padded_rows
