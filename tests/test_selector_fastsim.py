"""Online policy selection (Alg. 2 / Thm. 2) + fast-sim parity + Thm. 1 trend."""
import numpy as np
import pytest

from _hyp_compat import given, settings, st
from repro.configs.base import JobConfig, ThroughputConfig
from repro.core import fast_sim
from repro.core.job import normalize_utility
from repro.core.market import constant_trace, vast_like_trace
from repro.core.offline_opt import solve_offline
from repro.core.policies import AHAP, AHAPParams, RandDeadline, RandDeadlineParams
from repro.core.policies import uniform_commit_frac
from repro.core.policy_pool import (
    baseline_specs,
    paper_pool,
    rand_deadline_pool,
    robust_pool,
    specs_to_arrays,
    uniform_rand_deadline_pool,
)
from repro.core.predictor import NoisyPredictor, PerfectPredictor
from repro.core.selector import (
    best_policy,
    init_selector,
    regret,
    regret_bound,
    select,
    update,
)
from repro.core.simulator import simulate

JOB = JobConfig(workload=80, deadline=10, n_min=1, n_max=12, value=120.0)
TPUT = ThroughputConfig(mu1=0.9, mu2=0.95)


# ---------------------------------------------------------------------------
# Theorem 2: regret bound
# ---------------------------------------------------------------------------

def test_regret_bound_random_utilities():
    rng = np.random.default_rng(0)
    M, K = 20, 400
    st = init_selector(M, K)
    means = rng.uniform(0.2, 0.8, M)
    for _ in range(K):
        u = np.clip(rng.normal(means, 0.1), 0, 1)
        st = update(st, u)
    assert regret(st) <= regret_bound(M, K), (regret(st), regret_bound(M, K))
    assert best_policy(st) == int(np.argmax(means))


def test_regret_bound_adversarial_switching():
    """Alternating adversary: bound must still hold."""
    M, K = 8, 300
    st = init_selector(M, K)
    for k in range(K):
        u = np.zeros(M)
        u[k % M] = 1.0
        st = update(st, u)
    assert regret(st) <= regret_bound(M, K) + 1e-9


def test_selector_converges_to_best():
    M, K = 10, 600
    st = init_selector(M, K)
    for _ in range(K):
        u = np.full(M, 0.4)
        u[3] = 0.6
        st = update(st, u)
    assert best_policy(st) == 3
    assert st.weights[3] > 0.9


def test_select_samples_from_weights():
    st = init_selector(4, 10)
    st.weights = np.array([0.0, 0.0, 1.0, 0.0])
    rng = np.random.default_rng(0)
    assert select(st, rng) == 2


# ---------------------------------------------------------------------------
# fast_sim parity with the reference simulator
# ---------------------------------------------------------------------------

def test_fast_sim_matches_reference():
    pool = paper_pool(omegas=(1, 3, 5), sigmas=(0.3, 0.7)) + baseline_specs()
    arrs = specs_to_arrays(pool)
    for seed in range(2):
        tr = vast_like_trace(seed=seed, days=1).window(0, 10)
        pred = NoisyPredictor(tr, "fixed_uniform", 0.2, seed=seed).matrix(
            fast_sim.W1MAX - 1
        )
        prices, avail, pm = fast_sim.prepare_inputs(tr, pred, JOB.deadline)
        out = fast_sim.simulate_pool(
            arrs, fast_sim.JobArrays.of(JOB), TPUT, prices, avail, pm
        )
        uj = np.asarray(out["utility"])
        for i, spec in enumerate(pool):
            r = simulate(spec.build(), JOB, TPUT, tr,
                         pred if spec.kind == 0 else None)
            assert abs(r.utility - uj[i]) < 1e-2, (spec.name, r.utility, uj[i])


def test_fast_sim_robust_ahap_matches_reference():
    """Robust-AHAP (rho < 1.0): the availability-discounted AHAP lanes must
    match the python AHAP policy (rho passes through AHAPParams) exactly —
    only the rho == 1.0 paths were cross-checked before."""
    pool = robust_pool(rhos=(0.5, 0.85), omegas=(3,), sigmas=(0.5, 0.9))
    assert all(s.rho < 1.0 for s in pool)
    arrs = specs_to_arrays(pool)
    for seed in range(2):
        tr = vast_like_trace(seed=10 + seed, days=1).window(0, 10)
        pred = NoisyPredictor(tr, "fixed_uniform", 0.3, seed=seed).matrix(
            fast_sim.W1MAX - 1
        )
        prices, avail, pm = fast_sim.prepare_inputs(tr, pred, JOB.deadline)
        out = fast_sim.simulate_pool(
            arrs, fast_sim.JobArrays.of(JOB), TPUT, prices, avail, pm
        )
        uj = np.asarray(out["utility"])
        for i, spec in enumerate(pool):
            r = simulate(spec.build(), JOB, TPUT, tr, pred)
            assert abs(r.utility - uj[i]) < 1e-2, (spec.name, r.utility, uj[i])


def test_fast_sim_rand_deadline_matches_reference():
    """RAND_DEADLINE lanes (randomized commitment thresholds,
    arXiv:2601.14612) must match the python RandDeadline policy on the cheap
    scan — including the f32 tau = floor(cfrac * d) commitment slot."""
    pool = rand_deadline_pool((0.1, 0.3, 0.55, 0.8, 0.95))
    arrs = specs_to_arrays(pool)
    for seed in range(3):
        tr = vast_like_trace(seed=20 + seed, days=1).window(0, 10)
        prices, avail, pm = fast_sim.prepare_inputs(tr, None, JOB.deadline)
        out = fast_sim.simulate_pool(
            arrs, fast_sim.JobArrays.of(JOB), TPUT, prices, avail, pm
        )
        uj = np.asarray(out["utility"])
        for i, spec in enumerate(pool):
            r = simulate(spec.build(), JOB, TPUT, tr)
            assert abs(r.utility - uj[i]) < 1e-2, (spec.name, r.utility, uj[i])


def test_fast_sim_uniform_rand_deadline_matches_reference():
    """The non-ski-rental RAND_DEADLINE family: quantile function F^{-1}(q)=q
    rides the pool's cfrac hook (rand_deadline_pool(qs, qfn=...)). The fast
    lanes must match the python RandDeadline built with the same explicit
    commitment fraction — and the encoding must be the identity, distinct
    from the ski-rental family's log1p curve."""
    qs = (0.1, 0.35, 0.6, 0.85)
    pool = uniform_rand_deadline_pool(qs)
    arrs = specs_to_arrays(pool)
    np.testing.assert_allclose(arrs["cfrac"], np.float32(qs))
    ski = specs_to_arrays(rand_deadline_pool(qs))["cfrac"]
    assert np.all(np.abs(arrs["cfrac"] - ski) > 1e-3)  # genuinely different
    assert [uniform_commit_frac(q) for q in qs] == list(qs)
    with pytest.raises(ValueError):  # a negative fraction would silently
        rand_deadline_pool((0.5,), qfn=lambda q: q - 1.0)  # hit the sentinel
    for seed in range(3):
        tr = vast_like_trace(seed=40 + seed, days=1).window(0, 10)
        prices, avail, pm = fast_sim.prepare_inputs(tr, None, JOB.deadline)
        out = fast_sim.simulate_pool(
            arrs, fast_sim.JobArrays.of(JOB), TPUT, prices, avail, pm
        )
        uj = np.asarray(out["utility"])
        for i, spec in enumerate(pool):
            r = simulate(spec.build(), JOB, TPUT, tr)
            assert abs(r.utility - uj[i]) < 1e-2, (spec.name, r.utility, uj[i])


@settings(max_examples=15, deadline=None)
@given(q=st.floats(0.02, 0.98), seed=st.integers(0, 500))
def test_rand_deadline_utility_and_feasibility(q, seed):
    """Properties of the randomized-commitment strategy: utility can never
    exceed the job value (cost >= 0), and every slot's decision respects the
    N^max / availability envelope on arbitrary markets."""
    rng = np.random.default_rng(seed)
    tr = vast_like_trace(seed=int(rng.integers(0, 10_000)), days=1).window(0, 10)
    r = simulate(RandDeadline(RandDeadlineParams(q)), JOB, TPUT, tr)
    assert r.utility <= JOB.value + 1e-6
    assert np.all(r.n_total <= JOB.n_max)
    assert np.all(r.n_spot <= np.asarray(tr.avail[: JOB.deadline], int))
    assert np.all(r.n_od >= 0) and np.all(r.n_spot >= 0)


@settings(max_examples=15, deadline=None)
@given(q=st.floats(0.02, 0.98))
def test_rand_deadline_feasible_market_meets_deadline(q):
    """Deadline feasibility: on a market with plentiful cheap spot the
    commitment strategy must finish by the deadline for EVERY quantile —
    pre-commitment it rides N^max spot, post-commitment it sizes on-demand
    to the remaining workload, and the capacity envelope
    (mu1 + (d-1)) * alpha * N^max covers L with a wide margin."""
    tr = constant_trace(price=0.3, avail=JOB.n_max, length=JOB.deadline + 1)
    r = simulate(RandDeadline(RandDeadlineParams(q)), JOB, TPUT, tr)
    assert r.completed_by_deadline, (q, r.completion_time)
    assert r.completion_time <= JOB.deadline
    assert r.utility <= JOB.value + 1e-6


def test_fast_sim_batched_lanes_match_vmap_oracle():
    """The lane-batched AHAP scan (one solve_window_batch call per slot)
    is bitwise-pinned to vmapping the per-lane scan (_simulate_one_ahap,
    the pre-batching formulation kept as the equivalence oracle), on the
    XLA and Pallas-interpret backends."""
    import jax
    import jax.numpy as jnp

    pool = [s for s in paper_pool(omegas=(1, 3, 5), sigmas=(0.3, 0.7))
            if s.kind == 0]
    arrs = specs_to_arrays(pool)
    w, v = jnp.asarray(arrs["omega"]), jnp.asarray(arrs["v"])
    sg, rho = jnp.asarray(arrs["sigma"]), jnp.asarray(arrs["rho"])
    tr = vast_like_trace(seed=8, days=1).window(0, 10)
    pred = NoisyPredictor(tr, "fixed_uniform", 0.2, seed=8).matrix(
        fast_sim.W1MAX - 1
    )
    prices, avail, pm = fast_sim.prepare_inputs(tr, pred, JOB.deadline)
    j = fast_sim.JobArrays.of(JOB)
    oracle = jax.vmap(
        lambda a, b, c, d: fast_sim._simulate_one_ahap(
            a, b, c, d, j, TPUT, prices, avail, pm, "xla"
        )
    )(w, v, sg, rho)
    for backend in ("xla", "pallas-interpret"):
        batched = fast_sim._simulate_lanes_ahap(
            w, v, sg, rho, j, TPUT, prices, avail, pm, backend
        )
        for k in oracle:
            np.testing.assert_array_equal(
                np.asarray(batched[k]), np.asarray(oracle[k]),
                err_msg=f"{k} [{backend}]",
            )


def test_fast_sim_partitioned_matches_monolithic():
    """The kind-partitioned pool path on a mixed pool — AHAP, AHANP,
    RAND_DEADLINE and the baselines, each kind's lanes scattered back to
    pool order — matches the reference simulator lane by lane: the same
    allocation every slot and the same utility."""
    pool = (paper_pool(omegas=(2, 4), sigmas=(0.4, 0.8))
            + rand_deadline_pool((0.2, 0.6)) + baseline_specs())
    assert len({s.kind for s in pool}) == 6
    arrs = specs_to_arrays(pool)
    tr = vast_like_trace(seed=5, days=1).window(0, 10)
    pred = NoisyPredictor(tr, "fixed_uniform", 0.2, seed=5).matrix(
        fast_sim.W1MAX - 1
    )
    prices, avail, pm = fast_sim.prepare_inputs(tr, pred, JOB.deadline)
    part = fast_sim.simulate_pool(
        arrs, fast_sim.JobArrays.of(JOB), TPUT, prices, avail, pm
    )
    assert np.shape(part["utility"]) == (len(pool),)
    for i, spec in enumerate(pool):
        r = simulate(spec.build(), JOB, TPUT, tr,
                     pred if spec.kind == 0 else None)
        assert abs(r.utility - float(part["utility"][i])) < 1e-2, spec.name
        np.testing.assert_array_equal(
            np.asarray(part["n_od"][i]), r.n_od, err_msg=spec.name)
        np.testing.assert_array_equal(
            np.asarray(part["n_spot"][i]), r.n_spot, err_msg=spec.name)


def test_pool_sizes_match_paper():
    assert len(paper_pool()) == 112          # 105 AHAP + 7 AHANP (unchanged)
    assert len(paper_pool(include_ahanp=False)) == 105
    assert len(paper_pool(fixed_v=1, include_ahanp=False)) == 35  # 5 omegas x 7 sigmas
    assert len(rand_deadline_pool()) == 9    # opt-in expansion: one per quantile
    assert len(paper_pool(rand_qs=(0.2, 0.5, 0.8))) == 115
    assert all(s.kind == 5 for s in rand_deadline_pool())


# ---------------------------------------------------------------------------
# Theorem 1 (empirical): gap to OPT shrinks with prediction error
# ---------------------------------------------------------------------------

def test_theorem1_gap_decreases_with_accuracy():
    gaps = {}
    for level in [0.0, 0.6]:
        g = []
        for seed in range(6):
            tr = vast_like_trace(seed=100 + seed, days=1).window(0, 10)
            opt = solve_offline(JOB, TPUT, tr)
            if level == 0.0:
                pred = PerfectPredictor(tr).matrix(5)
            else:
                pred = NoisyPredictor(tr, "magdep_heavytail", level, seed=seed).matrix(5)
            r = simulate(AHAP(AHAPParams(3, 1, 0.7)), JOB, TPUT, tr, pred)
            g.append(opt.utility - r.utility)
        gaps[level] = float(np.mean(g))
    assert gaps[0.0] <= gaps[0.6] + 1e-6, gaps
    assert gaps[0.0] >= -0.35  # OPT really is (near-)optimal


def test_normalized_utilities_feed_selector():
    tr = vast_like_trace(seed=0, days=1).window(0, 10)
    pool = paper_pool(omegas=(2,), sigmas=(0.5,))
    arrs = specs_to_arrays(pool)
    pred = PerfectPredictor(tr).matrix(fast_sim.W1MAX - 1)
    prices, avail, pm = fast_sim.prepare_inputs(tr, pred, JOB.deadline)
    out = fast_sim.simulate_pool(
        arrs, fast_sim.JobArrays.of(JOB), TPUT, prices, avail, pm
    )
    u = np.asarray(normalize_utility(JOB, out["utility"]))
    assert np.all((u >= 0) & (u <= 1))
