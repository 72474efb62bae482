"""Region-aware pool simulation: R=1 bitwise parity, reference parity,
migration-cost accounting, the hysteresis no-thrash property, and the
entry points' input rules."""
import numpy as np
import pytest

from repro.configs.base import JobConfig, ThroughputConfig
from repro.core import fast_sim
from repro.core.market import constant_trace, from_arrays, vast_like_trace
from repro.core.policies import (
    RSEL_AVAIL,
    RSEL_PRED,
    RSEL_PRICE,
)
from repro.core.policy_pool import (
    KIND_MSU,
    PolicySpec,
    baseline_specs,
    paper_pool,
    rand_deadline_pool,
    region_pool,
    specs_to_arrays,
)
from repro.core.predictor import NoisyPredictor, RegionalPredictor
from repro.core.region_market import (
    RegionalMarket,
    simulate_regional,
    vast_like_regions,
)

JOB = JobConfig(workload=80, deadline=10, n_min=1, n_max=12, value=120.0)
TPUT = ThroughputConfig(mu1=0.9, mu2=0.95)


def _mixed_pool():
    return (paper_pool(omegas=(1, 3, 5), sigmas=(0.3, 0.7))
            + rand_deadline_pool((0.2, 0.6)) + baseline_specs())


def test_r1_bitwise_parity_with_simulate_pool_jobs():
    """The acceptance pin: with one region, every simulate_pool_jobs leaf is
    BITWISE-identical through the region-aware scans (mixed AHAP + cheap
    kinds, region lanes' where-branches all passthrough), and no lane ever
    migrates."""
    arrs = specs_to_arrays(_mixed_pool())
    jobs_list = [JOB,
                 JobConfig(workload=100, deadline=10, n_min=2, n_max=14,
                           value=120.0)]
    stacked = fast_sim.stack_jobs(jobs_list)
    prices_l, avail_l, pm_l, rp_l, ra_l, rpm_l = [], [], [], [], [], []
    for seed in range(len(jobs_list)):
        tr = vast_like_trace(seed=30 + seed, days=1).window(0, 10)
        pred = NoisyPredictor(tr, "fixed_uniform", 0.2, seed=seed).matrix(
            fast_sim.W1MAX - 1
        )
        prices, avail, pm = fast_sim.prepare_inputs(tr, pred, JOB.deadline)
        rp, ra, rpm = fast_sim.prepare_inputs(
            RegionalMarket.from_traces([tr]), pred[None], JOB.deadline
        )
        prices_l.append(prices); avail_l.append(avail); pm_l.append(pm)
        rp_l.append(rp); ra_l.append(ra); rpm_l.append(rpm)
    single = fast_sim.simulate_pool_jobs(
        arrs, stacked, TPUT, np.stack(prices_l), np.stack(avail_l),
        np.stack(pm_l),
    )
    regional = fast_sim.simulate_pool_jobs(
        arrs, stacked, TPUT, np.stack(rp_l), np.stack(ra_l), np.stack(rpm_l),
        delta_mig=1,
    )
    for k in single:
        np.testing.assert_array_equal(
            np.asarray(single[k]), np.asarray(regional[k]), err_msg=k
        )
    assert np.all(np.asarray(regional["migrations"]) == 0)
    assert np.all(np.asarray(regional["region"]) == 0)


@pytest.mark.parametrize("entry", ["simulate_pool_jobs",
                                   "simulate_pool_jobs_sharded"])
@pytest.mark.parametrize("regional, kw", [
    (True, {}),
    (False, {"delta_mig": 1}),
    (False, {"p_od": 1.5}),
], ids=["region_axis_without_delta_mig", "delta_mig_on_flat_rows",
        "p_od_on_flat_rows"])
def test_entry_points_refuse_mismatched_region_options(entry, regional, kw):
    """The market's rank decides the region axis, and the options that
    belong to it follow: (J, R, d) rows without ``delta_mig`` are refused
    (no default may override a market's migration cost), and
    ``delta_mig`` or ``p_od`` with flat (J, d) rows is refused too."""
    arrs = specs_to_arrays(baseline_specs())
    tr = constant_trace(price=0.3, avail=6, length=JOB.deadline + 1)
    market = RegionalMarket.from_traces([tr, tr]) if regional else tr
    prices, avail, pm = fast_sim.prepare_inputs(market, None, JOB.deadline)
    with pytest.raises(ValueError, match="delta_mig"):
        getattr(fast_sim, entry)(
            arrs, fast_sim.stack_jobs([JOB]), TPUT, prices[None],
            avail[None], pm[None], **kw,
        )


def test_regions_sharded_single_device_fallback_bitwise():
    """simulate_pool_jobs_sharded on regional inputs must fall through to
    (and bitwise-match) simulate_pool_jobs on one visible device, for the
    default mesh and explicit 1-device meshes of either rank. The real
    multi-device parity (jobs / lanes / 2-D layouts under 4 forced host
    devices) runs in the tests/test_sharded_pool.py subprocess."""
    import jax

    from repro.launch.mesh import make_pool_mesh

    assert jax.device_count() == 1
    mkt = vast_like_regions(3, seed=5, days=1).window(0, 11)
    rpred = RegionalPredictor(
        mkt, lambda t, r: NoisyPredictor(t, "fixed_uniform", 0.2, seed=r)
    ).matrix(fast_sim.W1MAX - 1)
    arrs = specs_to_arrays(region_pool())
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, rpred, JOB.deadline)
    stacked = fast_sim.stack_jobs([JOB])
    tile = lambda x: np.asarray(x)[None]
    base = fast_sim.simulate_pool_jobs(
        arrs, stacked, TPUT, tile(rp), tile(ra), tile(rpm), delta_mig=1
    )
    for mesh in (None, make_pool_mesh(), make_pool_mesh(shape=(1, 1))):
        sh = fast_sim.simulate_pool_jobs_sharded(
            arrs, stacked, TPUT, tile(rp), tile(ra), tile(rpm),
            delta_mig=1, mesh=mesh,
        )
        for k in base:
            np.testing.assert_array_equal(
                np.asarray(base[k]), np.asarray(sh[k]), err_msg=k
            )


def test_region_lanes_match_python_reference():
    """Every region_pool lane (AHAP/AHANP/MSU/UP x strategy x margin) agrees
    with the python reference simulator (simulate_regional +
    policies.RegionSelector) on a 3-region phase-shifted market — utility,
    migration count, and per-slot region path."""
    mkt = vast_like_regions(3, seed=1, days=1).window(0, 11)
    rpred = RegionalPredictor(
        mkt, lambda t, r: NoisyPredictor(t, "fixed_uniform", 0.2, seed=r)
    ).matrix(fast_sim.W1MAX - 1)
    pool = region_pool()
    arrs = specs_to_arrays(pool)
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, rpred, JOB.deadline)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([JOB]), TPUT,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=mkt.delta_mig,
    )
    uj = np.asarray(out["utility"])[0]
    migs = np.asarray(out["migrations"])[0]
    regions = np.asarray(out["region"])[0]
    for i, spec in enumerate(pool):
        r = simulate_regional(
            spec.build(), spec.build_selector(), JOB, TPUT, mkt,
            np.asarray(rpm),
        )
        assert abs(r.utility - uj[i]) < 1e-2, (spec.name, r.utility, uj[i])
        assert r.migrations == int(migs[i]), spec.name
        # the reference breaks out of its loop on completion; compare the
        # region path only up to that point
        done_at = len(r.region_hist)
        if r.completed_by_deadline:
            done_at = int(np.ceil(r.completion_time))
        np.testing.assert_array_equal(
            regions[i, :done_at], r.region_hist[:done_at], err_msg=spec.name
        )


def test_migration_cost_accounting_two_region_toy():
    """Hand-checked 2-region crossover: MSU@greedy_price rides region 0's
    cheap spot for 4 slots, pays exactly one delta_mig slot (zero instances,
    zero billing) to move when the price advantage flips, then rides
    region 1. Cost and progress match the hand-derived numbers."""
    job = JobConfig(workload=200.0, deadline=8, n_min=1, n_max=4, value=120.0)
    tput = ThroughputConfig(alpha=1.0, beta=0.0, mu1=0.9, mu2=0.95)
    p0 = np.array([0.2] * 4 + [0.9] * 4)
    p1 = np.array([0.8] * 4 + [0.3] * 4)
    av = np.full(8, 4, np.int64)
    mkt = RegionalMarket.from_traces(
        [from_arrays(p0, av), from_arrays(p1, av)], delta_mig=1
    )
    spec = PolicySpec(KIND_MSU, rsel=RSEL_PRICE, rmargin=0.0)
    arrs = specs_to_arrays([spec])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, None, job.deadline)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([job]), tput,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=1,
    )
    region = np.asarray(out["region"])[0, 0]
    n_spot = np.asarray(out["n_spot"])[0, 0]
    np.testing.assert_array_equal(region, [0] * 4 + [1] * 4)
    # slot 4 is the checkpoint transfer: zero instances
    np.testing.assert_array_equal(n_spot, [4, 4, 4, 4, 0, 4, 4, 4])
    assert int(np.asarray(out["migrations"])[0, 0]) == 1
    # progress: mu1-discounted ramp after each 0->4 jump
    # slots 0-3: 0.9*4 + 4+4+4 = 15.6 ; slot 4: 0 ; slots 5-7: 3.6+4+4
    z_exp = 15.6 + 0.0 + 11.6
    assert abs(float(np.asarray(out["z_ddl"])[0, 0]) - z_exp) < 1e-4
    # billing: 4 slots at 0.2, the migration slot free, 3 slots at 0.3,
    # then the termination configuration finishes the remainder on-demand
    run_cost = 4 * 4 * 0.2 + 3 * 4 * 0.3
    term_cost = job.on_demand_price * job.n_max * (job.workload - z_exp) / 4.0
    assert abs(float(np.asarray(out["cost"])[0, 0])
               - (run_cost + term_cost)) < 1e-3
    # reference agrees
    ref = simulate_regional(spec.build(), spec.build_selector(), job, tput,
                            mkt, None)
    assert ref.migrations == 1
    assert abs(ref.cost - (run_cost + term_cost)) < 1e-3


def test_per_region_od_price_accounting_two_region_toy():
    """Hand-checked per-region on-demand pricing: the crossover toy of
    test_migration_cost_accounting_two_region_toy with od multipliers
    (1.0, 2.0). MSU@greedy_price selects regions on SPOT prices and never
    buys on-demand inside the window, so the region path, allocations and
    running spot cost are unchanged — only the termination configuration,
    billed at the final region's od rate, doubles. A scalar multiplier of
    1.0 must be a bitwise no-op (the shipped-program pin), and the python
    reference (market.p_od) must agree with the fast path."""
    job = JobConfig(workload=200.0, deadline=8, n_min=1, n_max=4, value=120.0)
    tput = ThroughputConfig(alpha=1.0, beta=0.0, mu1=0.9, mu2=0.95)
    p0 = np.array([0.2] * 4 + [0.9] * 4)
    p1 = np.array([0.8] * 4 + [0.3] * 4)
    av = np.full(8, 4, np.int64)
    traces = [from_arrays(p0, av), from_arrays(p1, av)]
    p_od = np.array([1.0, 2.0])
    mkt = RegionalMarket.from_traces(traces, delta_mig=1, p_od=p_od)
    spec = PolicySpec(KIND_MSU, rsel=RSEL_PRICE, rmargin=0.0)
    arrs = specs_to_arrays([spec])
    stacked = fast_sim.stack_jobs([job])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, None, job.deadline)
    tile = lambda x: np.asarray(x)[None]
    run = lambda po: fast_sim.simulate_pool_jobs(
        arrs, stacked, tput, tile(rp), tile(ra), tile(rpm),
        delta_mig=1, p_od=po,
    )
    out = run(mkt.p_od)
    # region path and allocations are untouched by the od multipliers
    np.testing.assert_array_equal(np.asarray(out["region"])[0, 0],
                                  [0] * 4 + [1] * 4)
    np.testing.assert_array_equal(np.asarray(out["n_spot"])[0, 0],
                                  [4, 4, 4, 4, 0, 4, 4, 4])
    assert not np.asarray(out["n_od"])[0, 0].any()
    # spot billing as in the base toy; termination finishes on-demand in
    # the final region (r1) at DOUBLE the flat od rate
    z_exp = 15.6 + 0.0 + 11.6
    run_cost = 4 * 4 * 0.2 + 3 * 4 * 0.3
    term = 2.0 * job.on_demand_price * job.n_max * (job.workload - z_exp) / 4.0
    assert abs(float(np.asarray(out["cost"])[0, 0]) - (run_cost + term)) < 1e-3
    # base toy (flat od) differs by exactly the doubled termination leg
    base = run(None)
    flat_term = term / 2.0
    assert abs(float(np.asarray(out["cost"])[0, 0])
               - float(np.asarray(base["cost"])[0, 0]) - flat_term) < 1e-3
    # scalar 1.0 multiplier: IEEE-exact no-op, every leaf bitwise
    ones = run(1.0)
    for k in base:
        np.testing.assert_array_equal(
            np.asarray(base[k]), np.asarray(ones[k]), err_msg=k
        )
    # the python reference sees market.p_od and lands on the same books
    ref = simulate_regional(spec.build(), spec.build_selector(), job, tput,
                            mkt, None)
    assert ref.migrations == 1
    np.testing.assert_array_equal(ref.region_hist, [0] * 4 + [1] * 4)
    assert abs(ref.cost - (run_cost + term)) < 1e-3
    assert abs(ref.cost - float(np.asarray(out["cost"])[0, 0])) < 1e-3
    # sharded entry forwards p_od (single-device fallthrough, bitwise)
    sh = fast_sim.simulate_pool_jobs_sharded(
        arrs, stacked, tput, tile(rp), tile(ra), tile(rpm),
        delta_mig=1, p_od=mkt.p_od,
    )
    for k in out:
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(sh[k]), err_msg=k
        )


def test_hysteresis_prevents_thrash():
    """Alternating-argmin market (price lead flips every slot by 0.05): the
    margin-0 greedy lane thrashes, the sticky lane (margin > oscillation)
    never migrates after free initial placement — and with a nonzero
    migration cost the sticky lane's utility strictly wins."""
    d = 10
    t = np.arange(d)
    p0 = 0.50 + 0.05 * (t % 2)
    p1 = 0.55 - 0.05 * (t % 2)
    av = np.full(d, 8, np.int64)
    mkt = RegionalMarket.from_traces(
        [from_arrays(p0, av), from_arrays(p1, av)], delta_mig=2
    )
    specs = [
        PolicySpec(KIND_MSU, rsel=RSEL_PRICE, rmargin=0.0),    # thrasher
        PolicySpec(KIND_MSU, rsel=RSEL_PRICE, rmargin=0.10),   # sticky
    ]
    arrs = specs_to_arrays(specs)
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, None, d)
    job = JobConfig(workload=200.0, deadline=d, n_min=1, n_max=8, value=120.0)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([job]), TPUT,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=2,
    )
    migs = np.asarray(out["migrations"])[0]
    assert migs[0] >= 3, migs          # greedy chases every flip
    assert migs[1] == 0, migs          # hysteresis holds the home region
    util = np.asarray(out["utility"])[0]
    assert util[1] > util[0], util     # thrash pays delta_mig repeatedly


def test_free_migration_when_delta_zero():
    """delta_mig=0 models preemptible-checkpoint-free moves: switches happen
    but no slot is lost and no allocation is zeroed."""
    d = 8
    p0 = np.array([0.2] * 4 + [0.9] * 4)
    p1 = np.array([0.8] * 4 + [0.3] * 4)
    av = np.full(d, 4, np.int64)
    mkt = RegionalMarket.from_traces(
        [from_arrays(p0, av), from_arrays(p1, av)], delta_mig=0
    )
    job = JobConfig(workload=200.0, deadline=d, n_min=1, n_max=4, value=120.0)
    tput = ThroughputConfig(alpha=1.0, beta=0.0, mu1=0.9, mu2=0.95)
    arrs = specs_to_arrays([PolicySpec(KIND_MSU, rsel=RSEL_PRICE)])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, None, d)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([job]), tput,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=0,
    )
    np.testing.assert_array_equal(np.asarray(out["n_spot"])[0, 0], [4] * d)
    assert int(np.asarray(out["migrations"])[0, 0]) == 1
    # cost: 4 slots at 0.2 then 4 at 0.3, no lost slot
    run_cost = 4 * 4 * 0.2 + 4 * 4 * 0.3
    z_exp = 0.9 * 4 + 7 * 4  # one mu1 ramp, constant 4 thereafter
    term = job.on_demand_price * 4 * (200.0 - z_exp) / 4.0
    assert abs(float(np.asarray(out["cost"])[0, 0]) - (run_cost + term)) < 1e-3


def test_no_migration_after_completion():
    """A job that finishes before the price lead flips must not be moved (or
    counted as migrating) by post-completion score changes — the reference
    loop stops at completion and the fast scan freezes the region state."""
    d = 10
    p0 = np.array([0.2] * 5 + [0.9] * 5)
    p1 = np.array([0.8] * 5 + [0.3] * 5)
    av = np.full(d, 8, np.int64)
    mkt = RegionalMarket.from_traces(
        [from_arrays(p0, av), from_arrays(p1, av)], delta_mig=1
    )
    # finishes in ~2 slots at n_max=8, long before the flip at t=5
    job = JobConfig(workload=10.0, deadline=d, n_min=1, n_max=8, value=120.0)
    spec = PolicySpec(KIND_MSU, rsel=RSEL_PRICE)
    arrs = specs_to_arrays([spec])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, None, d)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([job]), TPUT,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=1,
    )
    assert bool(np.asarray(out["completed"])[0, 0])
    assert int(np.asarray(out["migrations"])[0, 0]) == 0
    np.testing.assert_array_equal(np.asarray(out["region"])[0, 0], 0)
    ref = simulate_regional(spec.build(), spec.build_selector(), job, TPUT,
                            mkt, None)
    assert ref.migrations == 0


def test_no_migration_after_deadline_heterogeneous_batch():
    """In a stacked batch the scan runs dmax slots for every job; a job
    whose own deadline expired (missed, not completed) must not be moved by
    — or charged migrations for — score flips after its deadline."""
    dmax = 10
    p0 = np.array([0.2] * 6 + [0.9] * 4)
    p1 = np.array([0.8] * 6 + [0.3] * 4)
    av = np.full(dmax, 2, np.int64)
    mkt = RegionalMarket.from_traces(
        [from_arrays(p0, av), from_arrays(p1, av)], delta_mig=1
    )
    # job 0: deadline 5, huge workload -> misses, expires before the t=6
    # flip; job 1: deadline 10 -> legitimately migrates at the flip
    jobs = [
        JobConfig(workload=500.0, deadline=5, n_min=1, n_max=2, value=120.0),
        JobConfig(workload=500.0, deadline=dmax, n_min=1, n_max=2,
                  value=120.0),
    ]
    spec = PolicySpec(KIND_MSU, rsel=RSEL_PRICE)
    arrs = specs_to_arrays([spec])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, None, dmax)
    tile = lambda x: np.repeat(np.asarray(x)[None], 2, axis=0)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs(jobs), TPUT,
        tile(rp), tile(ra), tile(rpm), delta_mig=1,
    )
    migs = np.asarray(out["migrations"])[:, 0]
    assert migs[0] == 0 and migs[1] == 1, migs
    np.testing.assert_array_equal(np.asarray(out["region"])[0, 0], 0)
    for ji, job in enumerate(jobs):  # reference agrees per job
        ref = simulate_regional(spec.build(), spec.build_selector(), job,
                                TPUT, mkt, None)
        assert ref.migrations == int(migs[ji]), ji


def test_short_horizon_pred_scores_match_reference():
    """pred_horizon with a predictor horizon SHORTER than the scoring window:
    prepare_inputs edge-pads the forecast and the reference selector
    pads identically (RSEL_PRED_WINDOW), so both sides pick the same regions.
    Region 0 dangles a 2-slot teaser rate that a short forecast would
    overweight without the shared padding convention."""
    d, h = 8, 2  # h+1 = 3 < W1MAX = 6
    p0 = np.array([0.3, 0.3] + [0.9] * (d - 2))
    p1 = np.full(d, 0.5)
    av = np.full(d, 8, np.int64)
    mkt = RegionalMarket.from_traces(
        [from_arrays(p0, av), from_arrays(p1, av)], delta_mig=1
    )
    pred = RegionalPredictor(mkt).matrix(h)
    spec = PolicySpec(KIND_MSU, rsel=RSEL_PRED)
    arrs = specs_to_arrays([spec])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, pred, d)
    job = JobConfig(workload=500.0, deadline=d, n_min=1, n_max=8, value=120.0)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([job]), TPUT,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=1,
    )
    # the reference consumes the RAW (h+1)-entry forecast and pads inside
    # RegionSelector.scores; the fast path consumes the padded rpm
    ref = simulate_regional(spec.build(), spec.build_selector(), job, TPUT,
                            mkt, pred)
    np.testing.assert_array_equal(
        np.asarray(out["region"])[0, 0], ref.region_hist
    )
    assert int(np.asarray(out["migrations"])[0, 0]) == ref.migrations
    assert abs(float(np.asarray(out["utility"])[0, 0]) - ref.utility) < 1e-2


def test_greedy_avail_follows_capacity():
    """greedy_avail ignores price and tracks the deeper pool."""
    d = 6
    av0 = np.array([8, 8, 8, 1, 1, 1], np.int64)
    av1 = np.array([1, 1, 1, 8, 8, 8], np.int64)
    pr = np.full(d, 0.5)
    mkt = RegionalMarket.from_traces(
        [from_arrays(pr, av0), from_arrays(pr, av1)], delta_mig=0
    )
    arrs = specs_to_arrays([PolicySpec(KIND_MSU, rsel=RSEL_AVAIL)])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, None, d)
    job = JobConfig(workload=500.0, deadline=d, n_min=1, n_max=8, value=120.0)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([job]), TPUT,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=0,
    )
    np.testing.assert_array_equal(
        np.asarray(out["region"])[0, 0], [0, 0, 0, 1, 1, 1]
    )


def test_pred_horizon_lane_uses_forecasts():
    """pred_horizon scores average the forecast window: a region that is
    cheap now but predicted to collapse loses to a region predicted cheap
    throughout."""
    d, h = 6, fast_sim.W1MAX - 1
    # region 0: cheap at t=0 but predicted expensive after; region 1: flat 0.5
    p0 = np.array([0.3] + [1.0] * (d - 1))
    p1 = np.full(d, 0.5)
    av = np.full(d, 8, np.int64)
    mkt = RegionalMarket.from_traces(
        [from_arrays(p0, av), from_arrays(p1, av)], delta_mig=1
    )
    pred = RegionalPredictor(mkt).matrix(h)  # perfect foresight
    arrs = specs_to_arrays([
        PolicySpec(KIND_MSU, rsel=RSEL_PRICE),
        PolicySpec(KIND_MSU, rsel=RSEL_PRED),
    ])
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, pred, d)
    job = JobConfig(workload=500.0, deadline=d, n_min=1, n_max=8, value=120.0)
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs([job]), TPUT,
        np.asarray(rp)[None], np.asarray(ra)[None], np.asarray(rpm)[None],
        delta_mig=1,
    )
    region = np.asarray(out["region"])[0]
    assert region[0, 0] == 0          # greedy-price grabs the teaser rate
    assert np.all(region[1] == 1)     # pred-horizon sees through it
    # the predictive lane never pays the migration the greedy lane must make
    migs = np.asarray(out["migrations"])[0]
    assert migs[1] == 0 and migs[0] >= 1
    util = np.asarray(out["utility"])[0]
    assert util[1] > util[0]
