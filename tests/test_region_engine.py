"""Regional selection engine (core.engine on region-axis inputs + the
streamed device-side prediction prep).

Pins, per the engine's contracts:
  * R == 1 regional runs are BITWISE-identical to the single-region
    engine on the squeezed inputs (weights, trajectories, mean utility);
  * region ``job_chunk`` streaming is bitwise-equal to the unchunked run
    across chunk sizes 1 / dividing / == K / non-dividing / > K;
  * a ``prep=`` callable produces the exact run the pre-built arrays do
    (the double-buffered staging changes scheduling, not values);
  * ``prepare_noisy_inputs_regions``'s numpy path is bitwise-equal to the
    per-job ``RegionalPredictor`` constructions it replaces (seed
    convention ``seeds[k] * 1009 + r``);
  * ``prep_backend="jax"`` (the jitted batched-PRNG device prep) agrees
    with the numpy oracle on the WINNER and on the regret ratio — the
    draws come from a different PRNG, so parity is decision-level, not
    bitwise — and collapses to the exact true future at level 0;
  * ``collect=True`` regional engine runs carry ``tel_region`` /
    ``tel_migration`` whose ledger reconciliation holds, and an armed
    never-firing fallback monitor leaves every shared leaf bitwise.
"""
import numpy as np

from benchmarks.common import PAPER_TPUT, job_stream_arrays
from repro.chaos import FallbackConfig
from repro.core import engine, fast_sim
from repro.core import selector as sel
from repro.core.policy_pool import (
    baseline_specs,
    paper_pool,
    rand_deadline_pool,
    region_pool,
    specs_to_arrays,
)
from repro.core.predictor import NoisyPredictor, RegionalPredictor
from repro.core.region_market import vast_like_regions
from repro.obs import ledger

DEADLINE = 10
KIND, LEVEL, SEED = "fixed_uniform", 0.2, 7


def _workload(n_jobs: int, n_regions: int = 3, days: float = 2.0):
    market = vast_like_regions(n_regions, seed=13, days=days, delta_mig=1)
    rng = np.random.default_rng(SEED)
    jobs = job_stream_arrays(rng, n_jobs, DEADLINE)
    t0s = rng.integers(0, len(market) - DEADLINE - 1, size=n_jobs)
    seeds = SEED * 100003 + np.arange(n_jobs)
    return market, jobs, t0s, seeds


def _region_run(market, jobs, t0s, seeds, arrs, **kw):
    rp, ra, rpm = engine.prepare_noisy_inputs_regions(
        market, t0s, DEADLINE, KIND, LEVEL, seeds
    )
    return engine.simulate_and_select(
        arrs, jobs, PAPER_TPUT, rp, ra, rpm,
        delta_mig=market.delta_mig, **kw,
    )


def _assert_results_equal(a, b, bitwise_mean: bool = True):
    np.testing.assert_array_equal(np.asarray(a.state.weights),
                                  np.asarray(b.state.weights))
    np.testing.assert_array_equal(np.asarray(a.max_weight),
                                  np.asarray(b.max_weight))
    np.testing.assert_array_equal(np.asarray(a.regret), np.asarray(b.regret))
    if bitwise_mean:
        np.testing.assert_array_equal(a.mean_utility, b.mean_utility)
    else:
        np.testing.assert_allclose(a.mean_utility, b.mean_utility,
                                   rtol=1e-5, atol=1e-3)


def test_r1_engine_bitwise_matches_single_region():
    """The acceptance pin: with one region, the regional engine path
    (region scans + the shared normalize/EG legs) lands bitwise on the
    single-region engine's result. The regional prep seeds region 0 with
    ``seeds[k] * 1009``, so the single-region run uses those seeds and the
    forecast stacks are identical by construction."""
    market, jobs, t0s, seeds = _workload(10, n_regions=1)
    arrs = specs_to_arrays(paper_pool(omegas=(1, 3), sigmas=(0.3,))
                           + rand_deadline_pool((0.2,)) + baseline_specs())
    p, a, m = engine.prepare_noisy_inputs(
        market.region(0), t0s, DEADLINE, KIND, LEVEL, seeds * 1009
    )
    rp, ra, rpm = engine.prepare_noisy_inputs_regions(
        market, t0s, DEADLINE, KIND, LEVEL, seeds
    )
    np.testing.assert_array_equal(rp[:, 0], p)
    np.testing.assert_array_equal(ra[:, 0], a)
    np.testing.assert_array_equal(rpm[:, 0], m)
    single = engine.simulate_and_select(arrs, jobs, PAPER_TPUT, p, a, m)
    regional = engine.simulate_and_select(
        arrs, jobs, PAPER_TPUT, rp, ra, rpm, delta_mig=market.delta_mig
    )
    _assert_results_equal(single, regional)
    assert single.best_policy() == regional.best_policy()


def test_region_engine_chunked_equals_unchunked():
    """Streaming the job axis through the region path must not change the
    selection: trajectories and final weights bitwise, for chunk sizes
    1 / dividing / == K / non-dividing / > K."""
    market, jobs, t0s, seeds = _workload(12)
    arrs = specs_to_arrays(region_pool())
    base = _region_run(market, jobs, t0s, seeds, arrs)
    for chunk in (1, 3, 4, 5, 12, 20):
        out = _region_run(market, jobs, t0s, seeds, arrs, job_chunk=chunk)
        _assert_results_equal(base, out, bitwise_mean=False)


def test_region_engine_prep_callable_matches_arrays():
    """``prep=`` streaming (the double-buffered path) must produce the
    same chunk inputs the pre-built arrays slice to — results bitwise."""
    market, jobs, t0s, seeds = _workload(12)
    arrs = specs_to_arrays(region_pool())
    base = _region_run(market, jobs, t0s, seeds, arrs, job_chunk=5)
    prep = lambda lo, hi: engine.prepare_noisy_inputs_regions(
        market, t0s[lo:hi], DEADLINE, KIND, LEVEL, seeds[lo:hi]
    )
    streamed = engine.simulate_and_select(
        arrs, jobs, PAPER_TPUT, None, None, None,
        delta_mig=market.delta_mig, job_chunk=5, prep=prep,
    )
    _assert_results_equal(base, streamed)


def test_prepare_noisy_inputs_regions_matches_per_job_constructions():
    """The batched numpy prep row (k, r) is bitwise the per-job
    ``RegionalPredictor(market.window(t0), lambda tr, r:
    NoisyPredictor(tr, ..., seed=seeds[k]*1009+r))`` construction it
    replaced in the host loop."""
    market, _, t0s, seeds = _workload(6)
    rp, ra, rpm = engine.prepare_noisy_inputs_regions(
        market, t0s, DEADLINE, KIND, LEVEL, seeds
    )
    for k, (t0, s) in enumerate(zip(t0s, seeds)):
        w = market.window(int(t0), DEADLINE + 1)
        np.testing.assert_array_equal(
            rp[k], w.prices[:, :DEADLINE].astype(np.float32))
        np.testing.assert_array_equal(
            ra[k], w.avail[:, :DEADLINE].astype(np.int64))
        want = RegionalPredictor(
            w, lambda tr, r, s=s: NoisyPredictor(
                tr, KIND, LEVEL, seed=int(s) * 1009 + r)
        ).matrix(fast_sim.W1MAX - 1)[:, :DEADLINE].astype(np.float32)
        np.testing.assert_array_equal(rpm[k], want, err_msg=f"job {k}")


def test_jax_prep_zero_level_is_exact_truth():
    """At level 0 the jitted device prep has nothing to draw: its stack
    must equal the numpy oracle's (the edge-padded true future) exactly."""
    market, _, t0s, seeds = _workload(4)
    np_prep = engine.prepare_noisy_inputs_regions(
        market, t0s, DEADLINE, KIND, 0.0, seeds, prep_backend="numpy"
    )
    jx_prep = engine.prepare_noisy_inputs_regions(
        market, t0s, DEADLINE, KIND, 0.0, seeds, prep_backend="jax"
    )
    for a, b in zip(np_prep, jx_prep):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_jax_prep_winner_and_regret_parity():
    """``prep_backend="jax"`` draws from JAX's counter-based PRNG — not
    bitwise vs the numpy Philox oracle — so the pin is decision-level:
    same winning lane, regret ratio within a tight band, both under the
    Theorem 2 bound."""
    market, jobs, t0s, seeds = _workload(12)
    arrs = specs_to_arrays(region_pool())
    results = {}
    for backend in ("numpy", "jax"):
        rp, ra, rpm = engine.prepare_noisy_inputs_regions(
            market, t0s, DEADLINE, KIND, LEVEL, seeds, prep_backend=backend
        )
        results[backend] = engine.simulate_and_select(
            arrs, jobs, PAPER_TPUT, rp, ra, rpm, delta_mig=market.delta_mig
        )
    assert results["numpy"].best_policy() == results["jax"].best_policy()
    rr_np = results["numpy"].regret_ratio()
    rr_jx = results["jax"].regret_ratio()
    assert abs(rr_np - rr_jx) < 0.05, (rr_np, rr_jx)
    assert rr_np < 1.0 and rr_jx < 1.0


def test_region_engine_collect_reconciles_and_fallback_is_inert():
    """``collect=True`` through the regional engine: the chunk-concatenated
    ``sim_out`` carries the migration series, whose ledger reconciliation
    (slot sums == ``migrations`` leaves, ``tel_region`` == ``region``)
    must hold across chunk boundaries; an armed monitor whose threshold is
    never crossed leaves every shared leaf bitwise-identical and adds the
    all-quiet ``tel_fallback`` series."""
    market, jobs, t0s, seeds = _workload(8)
    arrs = specs_to_arrays(region_pool())
    base = _region_run(market, jobs, t0s, seeds, arrs, job_chunk=3)
    res = _region_run(market, jobs, t0s, seeds, arrs, job_chunk=3,
                      collect=True)
    _assert_results_equal(base, res)
    assert base.sim_out is None and res.sim_out is not None
    assert res.entropy is not None and res.top_policy is not None
    recon = ledger.migration_reconciliation(res.sim_out)
    assert recon["events_reconciled"], recon
    assert recon["series_matches_leaf"], recon
    # huge threshold: the monitor is armed but never trips — the AHANP
    # override is never selected, so the program's outputs are unchanged
    quiet = _region_run(market, jobs, t0s, seeds, arrs, job_chunk=3,
                        collect=True, fallback=FallbackConfig(threshold=1e9))
    _assert_results_equal(res, quiet)
    assert "tel_fallback" in quiet.sim_out
    assert not np.asarray(quiet.sim_out["tel_fallback"]).any()
    for k in res.sim_out:
        np.testing.assert_array_equal(
            np.asarray(res.sim_out[k]), np.asarray(quiet.sim_out[k]),
            err_msg=k,
        )
