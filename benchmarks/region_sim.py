"""Multi-region pool simulation benchmark (the SkyNomad scenario).

Three Vast.ai-like regions share one diurnal demand cycle phase-shifted by
8 hours each (it is always midday somewhere), in the paper's evaluation
regime (scarce availability, strong cycle). Jobs with 8-hour deadlines land
on random windows, so every job starts at a different point of the cycle.

Comparators:
  single-region   the compact scheduling slate (region_pool's base: AHAP
                  corners + AHANP + MSU + UP) pinned to each region
                  separately — best mean utility over (lane, region) is the
                  strongest thing a single-region scheduler could do.
  region lanes    the same slate crossed with region-selection strategies
                  (greedy-price / greedy-avail / predicted-horizon, plain
                  and sticky) via fast_sim.simulate_pool_jobs, paying
                  ``delta_mig`` checkpoint-transfer slots per move.

The headline `region_sim_gain` row is (best region lane - best single
region) mean utility; the acceptance bar is gain > 0 — migration must beat
the best fixed region even after paying for its moves. Rows are also folded
into BENCH_pool_sim.json (region rows replaced in place, the rest of the
file untouched).

Env knobs: REGION_SIM_JOBS (default 16), REGION_SIM_REPEAT (default 3);
POOL_SIM_MESH picks the pool mesh for the sharded region entry point
(shared with pool_sim_bench; single device falls back bitwise to the
unsharded path).
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmarks.common import PAPER_TPUT, job_stream, merge_bench_rows
from benchmarks.pool_sim_bench import _JSON_PATH

N_JOBS = int(os.environ.get("REGION_SIM_JOBS", "16"))
REPEAT = int(os.environ.get("REGION_SIM_REPEAT", "3"))
N_REGIONS = 3
DEADLINE = 16          # 8 hours of 30-min slots: spans half a phase offset
DELTA_MIG = 1


def _market():
    from repro.core.region_market import vast_like_regions

    # paper_market's scarce regime (benchmarks/common.py), regionalized:
    # phases 8h apart so availability droughts never align across regions
    return vast_like_regions(
        N_REGIONS, seed=13, days=4,
        phase_hours=(0.0, 8.0, 16.0),
        mean_price=0.7, price_sigma=0.5,
        avail_mean=5.5, avail_season_amp=3.0,
        delta_mig=DELTA_MIG,
    )


def _workload(n_jobs: int):
    from repro.core import fast_sim
    from repro.core.predictor import NoisyPredictor

    rng = np.random.default_rng(23)
    jobs = list(job_stream(rng, n_jobs, deadline=DEADLINE))
    market = _market()
    t0s = [int(rng.integers(0, len(market) - DEADLINE - 1))
           for _ in range(n_jobs)]
    wins = [market.window(t0, DEADLINE + 1) for t0 in t0s]
    prices = np.stack([w.prices[:, :DEADLINE] for w in wins]).astype(np.float32)
    avail = np.stack([w.avail[:, :DEADLINE] for w in wins]).astype(np.int64)
    preds = np.stack([
        np.stack([
            NoisyPredictor(w.region(r), "fixed_uniform", 0.2,
                           seed=i * N_REGIONS + r).matrix(
                fast_sim.W1MAX - 1
            )[:DEADLINE]
            for r in range(N_REGIONS)
        ])
        for i, w in enumerate(wins)
    ]).astype(np.float32)
    return jobs, prices, avail, preds


def _bench(fn, repeat: int = REPEAT) -> float:
    jax.block_until_ready(fn()["utility"])
    t0 = time.perf_counter()
    for _ in range(repeat):
        jax.block_until_ready(fn()["utility"])
    return (time.perf_counter() - t0) / repeat


def _update_bench_json(rows, extra):
    """Fold the region rows into BENCH_pool_sim.json without disturbing the
    other modules' rows (shared merge in benchmarks.common)."""
    merge_bench_rows(_JSON_PATH, "region_sim", "region", rows, extra)


def run():
    from repro.core import fast_sim
    from repro.core.policy_pool import region_pool, specs_to_arrays
    from repro.launch.mesh import make_pool_mesh, parse_pool_mesh_shape

    # same mesh knob as pool_sim_bench: the sharded region entry falls back
    # bitwise to simulate_pool_jobs on one device, so the headline gain
    # numbers are identical either way — only the throughput row scales
    mesh = make_pool_mesh(
        shape=parse_pool_mesh_shape(os.environ.get("POOL_SIM_MESH", ""))
    )
    jobs, prices, avail, preds = _workload(N_JOBS)
    stacked = fast_sim.stack_jobs(jobs)

    region_specs = region_pool()               # base slate x strategies
    base_specs = region_pool(strategies=(0,), margins=(0.0,))  # slate, fixed
    r_arrs = specs_to_arrays(region_specs)
    b_arrs = specs_to_arrays(base_specs)

    # best single-region lane: the base slate pinned to each region
    best_single, single_util = -np.inf, {}
    for r in range(N_REGIONS):
        out = fast_sim.simulate_pool_jobs(
            b_arrs, stacked, PAPER_TPUT,
            prices[:, r], avail[:, r], preds[:, r],
        )
        u = np.asarray(out["utility"]).mean(axis=0)
        single_util[r] = float(u.max())
        best_single = max(best_single, single_util[r])

    region_fn = lambda: fast_sim.simulate_pool_jobs_sharded(
        r_arrs, stacked, PAPER_TPUT, prices, avail, preds,
        delta_mig=DELTA_MIG, mesh=mesh,
    )
    secs = _bench(region_fn)
    out = region_fn()
    u_region = np.asarray(out["utility"]).mean(axis=0)
    best_region = float(u_region.max())
    best_lane = region_specs[int(u_region.argmax())].name
    mean_migs = float(np.asarray(out["migrations"]).mean())

    work_units = DEADLINE * len(region_specs) * N_JOBS * N_REGIONS
    rows = [
        ("region_sim_regions", secs * 1e6, work_units / secs),
        ("region_sim_best_single", 0.0, best_single),
        ("region_sim_best_region_lane", 0.0, best_region),
        ("region_sim_gain", 0.0, best_region - best_single),
        ("region_sim_mean_migrations", 0.0, mean_migs),
    ]
    _update_bench_json(rows, {
        "workload": {
            "regions": N_REGIONS, "jobs": N_JOBS, "slots": DEADLINE,
            "delta_mig": DELTA_MIG, "lanes": len(region_specs),
        },
        "best_region_lane": best_lane,
        "single_region_best_utilities": single_util,
        "gain": best_region - best_single,
    })
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows

    print_rows(run())
