"""The benchmark's copies of the program's generators draw bitwise what the
originals draw, and the reference forecast equals the program's forecast
stack row for row."""
import numpy as np
import pytest

from bench.gen.jobs import draw_jobs
from bench.gen.market import spot_trace
from bench.gen.traffic import Traffic, pool_lanes, seed_words
from bench.harness import Cell
from bench.ref.forecast import forecast

PAPER_JOBS = {"workload": [70, 120], "deadline": 10, "n_min": [1, 4],
              "n_max": [12, 17], "value": 120.0, "gamma": 2.0,
              "on_demand_price": 1.0}


def test_jobs_match_job_stream_arrays():
    from benchmarks.common import job_stream_arrays

    want = job_stream_arrays(np.random.default_rng(7), 257, 10)
    got = draw_jobs(np.random.default_rng(7), 257, PAPER_JOBS)
    for field in want._fields:
        a, b = np.asarray(getattr(want, field)), got[field]
        assert a.dtype == b.dtype, field
        np.testing.assert_array_equal(a, b, err_msg=field)


@pytest.mark.parametrize("kw", [
    {"seed": 21, "days": 40},
    {"seed": 3, "days": 2, "avail_mean": 9.0, "rho": 0.5},
])
def test_market_matches_paper_market(kw):
    from benchmarks.common import paper_market

    want = paper_market(**kw)
    regime = dict(mean_price=0.7, price_sigma=0.5, avail_mean=5.5,
                  avail_season_amp=3.0)
    regime.update({k: v for k, v in kw.items() if k != "seed"})
    prices, avail = spot_trace(kw["seed"], **regime)
    np.testing.assert_array_equal(want.prices, prices)
    np.testing.assert_array_equal(want.avail, avail)
    assert avail.dtype == want.avail.dtype


@pytest.mark.parametrize("kind", ["fixed_uniform", "magdep_heavytail"])
def test_forecast_matches_program_stack(kind):
    from repro.core.predictor import noisy_matrix_batch

    prices, avail = spot_trace(5, days=2)
    rng = np.random.default_rng(1)
    t0s = rng.integers(0, len(prices) - 11, size=6)
    seeds = rng.integers(0, 2**62, size=6)
    idx = t0s[:, None] + np.arange(11)[None, :]
    want = noisy_matrix_batch(prices[idx], avail[idx], kind, 0.1, seeds, 5)
    for k in range(6):
        got = forecast(prices[idx[k]], avail[idx[k]], kind, 0.1, seeds[k], 5)
        np.testing.assert_array_equal(want[k], got)


def test_paper_pool_order_matches_program():
    from repro.core.policy_pool import (baseline_specs, paper_pool,
                                        rand_deadline_pool, specs_to_arrays)

    cell = Cell.load("select-paper-1000")
    want = specs_to_arrays(paper_pool() + rand_deadline_pool()
                           + baseline_specs())
    got = cell.entry.program_pool(pool_lanes(cell.config["pool"]))
    for k in ("kind", "omega", "v", "sigma", "cfrac"):
        np.testing.assert_array_equal(want[k], got[k], err_msg=k)
    assert len(got["kind"]) == 124


def test_same_seed_same_calls_and_large_seeds():
    cell = Cell.load("select-paper-1000")
    for seed in (0, 2**31 + 17, -3, 2**70):
        a = Traffic(cell.config, cell.traffic, seed)
        b = Traffic(cell.config, cell.traffic, seed)
        for i in (0, 5):
            x, y = a.call(i), b.call(i)
            for k in ("t0s", "noise_seeds"):
                np.testing.assert_array_equal(x[k], y[k])
            for k in x["jobs"]:
                np.testing.assert_array_equal(x["jobs"][k], y["jobs"][k])
        assert len(a.call(1)["t0s"]) == cell.traffic["jobs_per_call"]
    assert seed_words(-1) != seed_words(2**64 - 1)
