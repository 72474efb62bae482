"""Pallas window-DP kernel validation: the fused min-plus DP (interpret mode
executes the real kernel body on CPU) is pinned against the XLA solver paths,
the pure-jnp oracle, and brute force on randomized windows."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp_compat import given, settings, st
from repro.configs.base import JobConfig, ThroughputConfig
from repro.core.window_opt import (
    _dp_step_shifted_batch,
    _solve_xla_batch,
    _unit_cost_table,
    brute_force_window,
    solve_window,
    solve_window_batch,
)
from repro.kernels.ref import window_dp_ref
from repro.kernels.window_dp import window_dp

TPUT = ThroughputConfig(mu1=0.9, mu2=0.95)

job_st = st.builds(
    JobConfig,
    workload=st.floats(5.0, 150.0),
    deadline=st.integers(2, 12),
    n_min=st.integers(1, 3),
    n_max=st.integers(4, 16),
    value=st.floats(10.0, 300.0),
    gamma=st.floats(1.1, 3.0),
)


def _random_window(rng, job, w1):
    prices = rng.uniform(0.05, 1.5, w1).astype(np.float32)
    avail = rng.integers(0, 17, w1).astype(np.int32)
    z0 = float(rng.uniform(0, job.workload))
    std = int(rng.integers(0, w1 + 1))
    return prices, avail, z0, std


def _solve(job, prices, avail, z0, std, backend, table_n=16):
    n_o, n_s, obj = solve_window(
        job, TPUT, jnp.float32(z0), jnp.int32(std), prices, avail,
        job.on_demand_price, table_n=table_n, backend=backend,
    )
    return np.asarray(n_o), np.asarray(n_s), float(obj)


# ---------------------------------------------------------------------------
# kernel == XLA solver (exact: same candidates, same tie-breaking)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), w1=st.integers(1, 6), job=job_st)
def test_window_dp_kernel_matches_xla_solver(seed, w1, job):
    rng = np.random.default_rng(seed)
    prices, avail, z0, std = _random_window(rng, job, w1)
    ref = _solve(job, prices, avail, z0, std, "xla")
    seed_ref = _solve(job, prices, avail, z0, std, "xla-gather")
    pallas = _solve(job, prices, avail, z0, std, "pallas-interpret")
    for got in (seed_ref, pallas):
        np.testing.assert_array_equal(ref[0], got[0])
        np.testing.assert_array_equal(ref[1], got[1])
        assert abs(ref[2] - got[2]) < 1e-5 * (1 + abs(ref[2]))


# ---------------------------------------------------------------------------
# kernel == pure-jnp oracle on raw batched DP inputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,w1,tn", [(1, 6, 16), (8, 6, 16), (13, 3, 5), (40, 1, 4)])
def test_window_dp_kernel_matches_oracle_batched(b, w1, tn):
    rng = np.random.default_rng(b * 131 + w1)
    kw, u1 = tn + 1, w1 * tn + 1
    slot_cost = rng.uniform(0.0, 3.0, (b, w1, kw)).astype(np.float32)
    # price out a random subset of (slot, k) entries like the real table does
    slot_cost = np.where(rng.random((b, w1, kw)) < 0.3, 1.0e9, slot_cost)
    slot_cost[:, :, 0] = 0.0  # buying nothing is always free
    gain = np.cumsum(rng.uniform(0.0, 2.0, (b, u1)), axis=1).astype(np.float32)
    n_tot, obj = window_dp(jnp.asarray(slot_cost), jnp.asarray(gain),
                           interpret=True)
    n_ref, o_ref = window_dp_ref(jnp.asarray(slot_cost), jnp.asarray(gain))
    np.testing.assert_array_equal(np.asarray(n_tot), np.asarray(n_ref))
    np.testing.assert_allclose(np.asarray(obj), np.asarray(o_ref), rtol=1e-6)


def test_window_dp_kernel_under_vmap():
    """The pool simulator calls the kernel per-lane under vmap — the batching
    rule must agree with explicit batching."""
    rng = np.random.default_rng(3)
    b, w1, tn = 6, 4, 8
    slot_cost = rng.uniform(0.0, 3.0, (b, w1, tn + 1)).astype(np.float32)
    slot_cost[:, :, 0] = 0.0
    gain = np.cumsum(rng.uniform(0.0, 2.0, (b, w1 * tn + 1)), axis=1).astype(np.float32)
    direct = window_dp(jnp.asarray(slot_cost), jnp.asarray(gain), interpret=True)
    vmapped = jax.vmap(
        lambda c, g: window_dp(c[None], g[None], interpret=True)
    )(jnp.asarray(slot_cost), jnp.asarray(gain))
    np.testing.assert_array_equal(np.asarray(direct[0]), np.asarray(vmapped[0][:, 0]))
    np.testing.assert_allclose(np.asarray(direct[1]), np.asarray(vmapped[1][:, 0]),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# batched solve (one call per scan slot) == per-lane vmap path
# ---------------------------------------------------------------------------

def _random_lane_batch(rng, job, b, w1):
    prices = rng.uniform(0.05, 1.5, (b, w1)).astype(np.float32)
    avail = rng.integers(0, 17, (b, w1)).astype(np.int32)
    z0 = rng.uniform(0, job.workload, b).astype(np.float32)
    std = rng.integers(0, w1 + 1, b).astype(np.int32)
    return prices, avail, z0, std


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), w1=st.integers(1, 6),
       b=st.integers(1, 9), job=job_st)
def test_solve_window_batch_matches_vmap(seed, w1, b, job):
    """The in-scan batched DP (one (B, w1, tn+1) call — what the pool
    simulator issues per slot) must be BITWISE-equal per lane to vmapping
    the scalar solver, on the XLA and Pallas-interpret backends."""
    rng = np.random.default_rng(seed)
    prices, avail, z0, std = _random_lane_batch(rng, job, b, w1)
    vo, vs, vobj = jax.vmap(
        lambda z, s, p, a: solve_window(
            job, TPUT, z, s, p, a, job.on_demand_price, table_n=16,
            backend="xla",
        )
    )(z0, std, prices, avail)
    for backend in ("xla", "pallas-interpret"):
        bo, bs, bobj = solve_window_batch(
            job, TPUT, z0, std, prices, avail, job.on_demand_price,
            table_n=16, backend=backend,
        )
        np.testing.assert_array_equal(np.asarray(bo), np.asarray(vo), err_msg=backend)
        np.testing.assert_array_equal(np.asarray(bs), np.asarray(vs), err_msg=backend)
        np.testing.assert_allclose(
            np.asarray(bobj), np.asarray(vobj), rtol=1e-6, err_msg=backend
        )


def _solve_xla_batch_gather_backtrack(slot_cost, gain, tn):
    """The batched solver as it was with a per-lane gather in its backtrack
    (``take_along_axis`` on the choices and on the objective): the bitwise
    reference for the masked-reduction backtrack."""
    b, w1, _ = slot_cost.shape
    U = w1 * tn
    C0 = jnp.broadcast_to(
        jnp.where(jnp.arange(U + 1) == 0, 0.0, 1.0e9), (b, U + 1)
    )
    C, choices = jax.lax.scan(
        lambda C, row: _dp_step_shifted_batch(C, row, tn, U),
        C0, jnp.swapaxes(slot_cost, 0, 1),
    )
    obj = jnp.where(C < 1.0e9 / 2, gain - C, -jnp.inf)
    u_star = jnp.argmax(obj, axis=1)

    def back_step(u, choice_row):
        k = jnp.take_along_axis(choice_row, u[:, None], axis=1)[:, 0]
        return u - k, k

    _, k_rev = jax.lax.scan(back_step, u_star, choices, reverse=True)
    n_tot = jnp.swapaxes(k_rev, 0, 1).astype(jnp.int32)
    return n_tot, jnp.take_along_axis(obj, u_star[:, None], axis=1)[:, 0]


# the cells' shapes: 105 AHAP lanes a job, 1000 fleet rows; w1 = 6, tn = 16
@pytest.mark.parametrize(
    "b,w1,tn", [(1, 6, 16), (7, 4, 8), (24, 2, 5), (105, 6, 16), (1000, 6, 16)]
)
def test_solve_xla_batch_matches_oracle(b, w1, tn):
    """The lane-batched shifted-slice DP against the pure-jnp scan oracle and
    the gather-backtrack reference on raw batched tables (same randomized
    pricing-out as the kernel test). From two rows up the last two are edge
    rows: every k > 0 priced out, and exact ties in the objective (and in
    the DP's k)."""
    rng = np.random.default_rng(b * 17 + w1)
    kw, u1 = tn + 1, w1 * tn + 1
    slot_cost = rng.uniform(0.0, 3.0, (b, w1, kw)).astype(np.float32)
    slot_cost = np.where(rng.random((b, w1, kw)) < 0.3, 1.0e9, slot_cost)
    slot_cost[:, :, 0] = 0.0
    gain = np.cumsum(rng.uniform(0.0, 2.0, (b, u1)), axis=1).astype(np.float32)
    if b >= 2:
        slot_cost[-2, :, 1:] = 1.0e9
        # cost 0.5 a unit in every slot, gain 0.5 a unit plus 2 from u = 4:
        # the objective is exactly 2.0 for every u >= 4
        slot_cost[-1] = 0.5 * np.arange(kw, dtype=np.float32)
        units = np.arange(u1, dtype=np.float32)
        gain[-1] = 0.5 * units + np.where(units >= 4, 2.0, 0.0)
    args = (jnp.asarray(slot_cost), jnp.asarray(gain))
    n_tot, obj = _solve_xla_batch(*args, tn)
    n_old, o_old = _solve_xla_batch_gather_backtrack(*args, tn)
    n_ref, o_ref = window_dp_ref(*args)
    for n_want, o_want in ((n_old, o_old), (n_ref, o_ref)):
        np.testing.assert_array_equal(np.asarray(n_tot), np.asarray(n_want))
        np.testing.assert_array_equal(np.asarray(obj), np.asarray(o_want))
    if b >= 2:
        assert np.all(np.asarray(n_tot)[-2] == 0)
        assert np.asarray(n_tot)[-1].sum() == 4 and float(obj[-1]) == 2.0


def test_solve_xla_batch_lowers_without_gather():
    """The batched backtrack reads each row's choice by a dense masked
    reduction: at the cells' shape (B, w1, tn+1) = (1000, 6, 17) the lowered
    solver holds no gather, so a scattered per-lane read cannot come back
    unseen."""
    text = jax.jit(_solve_xla_batch, static_argnums=2).lower(
        jax.ShapeDtypeStruct((1000, 6, 17), jnp.float32),
        jax.ShapeDtypeStruct((1000, 6 * 16 + 1), jnp.float32),
        16,
    ).as_text()
    assert "gather" not in text


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), w1=st.integers(1, 3), b=st.integers(2, 4))
def test_solve_window_batch_matches_brute_force(seed, w1, b):
    """Every lane of a batched solve must achieve the brute-force objective
    (alpha = 1, beta = 0: the achieved plan utility is exact)."""
    from repro.core.job import tilde_value

    rng = np.random.default_rng(seed)
    job = JobConfig(
        workload=float(rng.uniform(5, 40)), deadline=int(rng.integers(2, 8)),
        n_min=1, n_max=int(rng.integers(2, 5)),
        value=float(rng.uniform(10, 100)), gamma=float(rng.uniform(1.2, 2.5)),
    )
    prices, avail, z0, std = _random_lane_batch(rng, job, b, w1)
    n_o, n_s, obj = solve_window_batch(
        job, TPUT, z0, std, prices, avail, job.on_demand_price,
        table_n=job.n_max, backend="xla",
    )
    n_o, n_s = np.asarray(n_o), np.asarray(n_s)
    for i in range(b):
        bf_obj, bf_plan = brute_force_window(
            job, TPUT, float(z0[i]), int(std[i]), prices[i], avail[i],
            job.on_demand_price,
        )
        z = float(z0[i]) + float((n_o[i] + n_s[i]).sum())
        cost = float((n_s[i] * prices[i]).sum()
                     + n_o[i].sum() * job.on_demand_price)
        u = float(tilde_value(job, TPUT, z)) - cost
        tol = 1e-3 * (1 + abs(bf_obj))
        assert abs(u - bf_obj) < tol, (i, u, bf_obj, bf_plan)
        assert abs(float(obj[i]) - bf_obj) < tol, (i, float(obj[i]), bf_obj)


# ---------------------------------------------------------------------------
# kernel == brute force (small windows, exact objective)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), w1=st.integers(1, 3))
def test_window_dp_kernel_matches_brute_force(seed, w1):
    rng = np.random.default_rng(seed)
    job = JobConfig(
        workload=float(rng.uniform(5, 40)), deadline=int(rng.integers(2, 8)),
        n_min=1, n_max=int(rng.integers(2, 5)),
        value=float(rng.uniform(10, 100)), gamma=float(rng.uniform(1.2, 2.5)),
    )
    prices, avail, z0, std = _random_window(rng, job, w1)
    n_o, n_s, obj = _solve(job, prices, avail, z0, std, "pallas-interpret",
                           table_n=job.n_max)
    bf_obj, bf_plan = brute_force_window(
        job, TPUT, z0, std, prices, avail, job.on_demand_price
    )
    # plans may tie; the achieved objective must be exact (alpha = 1, beta = 0)
    from repro.core.job import tilde_value

    z = z0 + float((n_o + n_s).sum())
    cost = float((n_s * prices).sum() + n_o.sum() * job.on_demand_price)
    u = float(tilde_value(job, TPUT, z)) - cost
    tol = 1e-3 * (1 + abs(bf_obj))
    assert abs(u - bf_obj) < tol, (u, obj, bf_obj, bf_plan)
    assert abs(obj - bf_obj) < tol, (obj, bf_obj)


# ---------------------------------------------------------------------------
# cost-table scaffolding sanity (shared by every backend)
# ---------------------------------------------------------------------------

def test_unit_cost_table_feasibility_pricing():
    job = JobConfig(workload=80, deadline=10, n_min=2, n_max=4, value=120.0)
    prices = jnp.asarray([0.5, 2.0, 0.3], jnp.float32)   # slot 1 above p_o
    avail = jnp.asarray([3, 5, 0], jnp.int32)
    slot_cost, spot_units, gain = _unit_cost_table(
        job, TPUT, 0.0, 2, prices, avail, 1.0, tn=4
    )
    slot_cost = np.asarray(slot_cost)
    assert np.all(slot_cost[:, 0] == 0.0)                 # k=0 free everywhere
    assert np.all(slot_cost[:, 1] >= 1.0e8)               # k=1 < n_min infeasible
    assert np.asarray(spot_units).tolist() == [3, 0, 0]   # pricey / past-deadline
    assert slot_cost[2, 2] >= 1.0e8                       # slot 2 beyond horizon
    # slot 0: 2 spot at 0.5 then od; slot 1: all od (price > p_o)
    assert abs(slot_cost[0, 3] - (3 * 0.5)) < 1e-6
    assert abs(slot_cost[1, 2] - 2.0) < 1e-6
    g = np.asarray(gain)
    assert g.shape == (3 * 4 + 1,) and np.all(np.diff(g) >= -1e-5)
