"""The plain reference against the program on the CPU, at small sizes.

On the TPU the float32 reference and the program agree cell for cell (the
check reads gaps of about 2e-5). XLA:CPU rounds some sums differently, and
the window problem has exact ties (on-demand costs the same in every slot),
so here the window solve may pick another of several equally good plans;
the tests pin that every disagreement is such a tie, and that the rules
without the window solve agree exactly.
"""
import ml_dtypes
import numpy as np
import pytest

from bench.gen.traffic import lane_arrays, pool_lanes
from bench.ref import sched as ref

TPUT = {"alpha": 1.0, "beta": 0.0, "mu1": 0.9, "mu2": 0.95}
CHEAP_POOL = [{"kind": "ahanp", "sigma": [0.3, 0.7, 0.9]},
              {"kind": "rand_deadline", "q": [0.2, 0.5, 0.8]},
              {"kind": "od_only"}, {"kind": "msu"}, {"kind": "up"}]


def _jobs(rng, n):
    return {"workload": rng.uniform(70, 120, n).astype(np.float32),
            "deadline": np.full(n, 10, np.int32),
            "n_min": rng.integers(1, 4, n).astype(np.int32),
            "n_max": rng.integers(12, 17, n).astype(np.int32),
            "value": np.full(n, 120, np.float32),
            "gamma": np.full(n, 2, np.float32),
            "p_o": np.full(n, 1, np.float32)}


def _market(rng, n, d):
    from bench.ref.forecast import forecast

    prices = rng.uniform(0.05, 1.3, (n, d + 1))
    avail = rng.integers(0, 17, (n, d + 1))
    pred = np.stack([forecast(prices[k], avail[k], "fixed_uniform", 0.1,
                              k, 5)[:d] for k in range(n)])
    return (prices[:, :d].astype(np.float32), avail[:, :d],
            pred.astype(np.float32))


def _program(pool):
    from repro.configs.base import ThroughputConfig
    from repro.core.policy_pool import KIND_NAMES, PolicySpec, specs_to_arrays

    code = {n: k for k, n in KIND_NAMES.items()}
    return (specs_to_arrays([PolicySpec(code[l["kind"]], l["omega"], l["v"],
                                        l["sigma"]) for l in pool]),
            ThroughputConfig(**TPUT))


def test_window_solve_disagrees_only_on_ties():
    import jax
    from repro.core import window_opt
    from repro.core.fast_sim import JobArrays, _job_cfg
    from repro.configs.base import ThroughputConfig

    rng = np.random.default_rng(0)
    b = 300
    job = {k: v[:1].repeat(b) for k, v in _jobs(rng, 1).items()}
    jb = ref.Jobs(job, np.float32)
    z = (rng.uniform(0, 1, b) * job["workload"]).astype(np.float32)
    horizon = rng.integers(1, 7, b).astype(np.int32)
    pr = rng.uniform(0.05, 1.3, (b, 6)).astype(np.float32)
    av = rng.integers(0, 17, (b, 6)).astype(np.int32)
    o1, s1 = ref.solve_window(jb, TPUT, z, horizon, pr, av)
    j = JobArrays(*[np.asarray(job[k][0]) for k in
                    ("workload", "deadline", "n_min", "n_max", "value",
                     "gamma", "p_o")])
    solve = jax.jit(lambda *a: window_opt.solve_window_batch(
        _job_cfg(j), ThroughputConfig(**TPUT), *a, j.p_o, table_n=16))
    o2, s2, _ = (np.asarray(x) for x in solve(z, horizon, pr, av))

    def objective(o, s):           # what the window problem maximises, f64
        jb64 = ref.Jobs(job, np.float64)
        cost = (s * pr.astype(float) + o).sum(axis=1)
        return ref.tilde_value(jb64, TPUT, z + (o + s).sum(axis=1)) - cost

    same = np.all((o1 == o2) & (s1 == s2), axis=1)
    assert same.mean() > 0.9
    np.testing.assert_allclose(objective(o1, s1), objective(o2, s2),
                               rtol=0, atol=1e-4)


def test_cheap_lanes_match_program_exactly():
    from repro.core import fast_sim

    rng = np.random.default_rng(1)
    lanes = pool_lanes(CHEAP_POOL)
    jobs = _jobs(rng, 12)
    p, a, pm = _market(rng, 12, 10)
    pool, tput = _program(lanes)
    want = np.asarray(fast_sim.simulate_pool_jobs(
        pool, fast_sim.JobArrays(**jobs), tput, p, a, pm)["utility"])
    got = ref.simulate_cells(jobs, lane_arrays(lanes), TPUT, p, a, pm)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)


def test_ahap_lanes_close_to_program():
    """Every lane kind at once; AHAP cells may take another plan of equal
    forecast value on the CPU, so most (not all) cells agree."""
    from repro.core import fast_sim

    rng = np.random.default_rng(2)
    lanes = pool_lanes([{"kind": "ahap", "omega": [1, 3, 5],
                         "sigma": [0.3, 0.9]}] + CHEAP_POOL)
    jobs = _jobs(rng, 6)
    p, a, pm = _market(rng, 6, 10)
    pool, tput = _program(lanes)
    want = np.asarray(fast_sim.simulate_pool_jobs(
        pool, fast_sim.JobArrays(**jobs), tput, p, a, pm)["utility"])
    got = ref.simulate_cells(jobs, lane_arrays(lanes), TPUT, p, a, pm)
    assert np.mean(np.abs(got - want) <= 1e-2) > 0.85


def test_eg_matches_program_scan():
    import jax.numpy as jnp
    from repro.core import selector

    rng = np.random.default_rng(4)
    un = rng.uniform(0, 1, (50, 9))
    st0 = selector.eg_init(9, 1000)
    st, traj = selector.run_eg_scan(st0, jnp.asarray(un, jnp.float32))
    w, cu, ce, regret = ref.eg_run(np.zeros(9), 0.0, float(st0.eta), un)
    np.testing.assert_allclose(np.asarray(st.weights), w, rtol=0, atol=1e-6)
    assert abs(float(traj["regret"][-1]) - regret) < 1e-4


@pytest.mark.parametrize("ft", [np.float32, ml_dtypes.bfloat16])
def test_reference_runs_in_each_precision(ft):
    rng = np.random.default_rng(5)
    lanes = pool_lanes([{"kind": "ahap", "omega": [2], "sigma": [0.5]}]
                       + CHEAP_POOL)
    jobs = _jobs(rng, 3)
    p, a, pm = _market(rng, 3, 10)
    u = ref.simulate_cells(jobs, lane_arrays(lanes), TPUT, p, a, pm, ft=ft)
    assert u.shape == (3, len(lanes)) and np.all(np.isfinite(u))
    assert np.all(u <= 120.0 + 1.0)
