"""End-to-end region-aware selection throughput: the regional engine vs
the host-loop pipeline it replaced.

The workload is the Fig. 9/10 convergence setting regionalized at paper
scale (1000 jobs x the 36-lane region pool x 16 slots x 3 phase-shifted
regions, fixed-magnitude uniform 10% noise). Two pipelines produce the
same selection decision:

  engine   core.engine.simulate_and_select in regional mode — a
           ``prepare_noisy_inputs_regions`` closure streams each job
           chunk's (K, R, d) market tensors + (K, R, d, W1MAX, 2)
           forecast stack (double-buffered: chunk k+1's host prep
           overlaps chunk k's async-dispatched device work), the
           simulate leg is ``simulate_pool_jobs_sharded``, and the
           fused normalize + EG lax.scan keeps the (K, M) utility matrix
           device-resident end to end.
  loop     the pre-engine pipeline: per-job ``RegionalPredictor`` /
           ``NoisyPredictor`` constructions (one python predictor per
           (job, region)), the same region simulation, then per-job
           ``normalize_utility`` calls and a K-iteration numpy
           ``selector.update`` loop.

Both pipelines draw identical forecasts (the engine's numpy prep is
bitwise-equal to the per-job constructions, seed convention
``seeds[k] * 1009 + r``), so ``region_e2e_same_winner`` is a
deterministic 1.0, not a statistical one. The headline
``region_e2e_engine_vs_loop`` row is loop-seconds over engine-seconds
(>= 1.0 means the engine pays for itself); the opt-in regression guard
(tests/test_bench_regression.py, RUN_BENCH_REGRESSION=1) pins both at
the 1000-job scale. ``region_e2e_prep_numpy`` vs ``region_e2e_prep_jax``
rows compare the host-numpy forecast stack against the jitted
batched-PRNG device path (``prep_backend="jax"``). Rows are folded into
BENCH_pool_sim.json (region_e2e rows replaced in place, the rest
untouched).

Env knobs: REGION_E2E_JOBS (default 1000), REGION_E2E_REPEAT (default
2), REGION_E2E_CHUNK (default 256 — the engine's streamed job-chunk
size); POOL_SIM_MESH picks the pool mesh for the sharded region
simulation (single device falls back bitwise to the unsharded path);
POOL_SIM_JSON redirects the JSON artifact.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from benchmarks.common import (
    PAPER_TPUT,
    job_stream_arrays,
    merge_bench_rows,
    numpy_eg_loop,
)
from benchmarks.pool_sim_bench import _JSON_PATH

N_JOBS = int(os.environ.get("REGION_E2E_JOBS", "1000"))
REPEAT = int(os.environ.get("REGION_E2E_REPEAT", "2"))
CHUNK = int(os.environ.get("REGION_E2E_CHUNK", "256"))
N_REGIONS = 3
DEADLINE = 16          # 8 hours of 30-min slots: spans half a phase offset
DELTA_MIG = 1
KIND, LEVEL, SEED = "fixed_uniform", 0.1, 7


def _market():
    from repro.core.region_market import vast_like_regions

    # region_sim's scarce regime, on a trace long enough that 1000 random
    # job windows land all over the diurnal cycle
    return vast_like_regions(
        N_REGIONS, seed=13, days=8,
        phase_hours=(0.0, 8.0, 16.0),
        mean_price=0.7, price_sigma=0.5,
        avail_mean=5.5, avail_season_amp=3.0,
        delta_mig=DELTA_MIG,
    )


def _workload(n_jobs: int = N_JOBS):
    rng = np.random.default_rng(SEED)
    market = _market()
    jobs = job_stream_arrays(rng, n_jobs, DEADLINE)
    t0s = rng.integers(0, len(market) - DEADLINE - 1, size=n_jobs)
    seeds = SEED * 100003 + np.arange(n_jobs)
    return market, jobs, t0s, seeds


def _timeit(fn, repeat: int = REPEAT):
    """(warm-up result, seconds per call at steady state)."""
    out = fn()
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return out, (time.perf_counter() - t0) / repeat


def _loop_pipeline(market, jobs_cfg, t0s, seeds, arrs):
    """The pre-engine regional host pipeline, end to end (returns the final
    numpy SelectorState). One python predictor per (job, region) — the
    construction cost the batched prep deletes — then the same region
    simulation and the per-job numpy EG loop."""
    from repro.core import fast_sim
    from repro.core.predictor import NoisyPredictor, RegionalPredictor

    prices, avail, preds = [], [], []
    for t0, s in zip(t0s, seeds):
        w = market.window(int(t0), DEADLINE + 1)
        prices.append(w.prices[:, :DEADLINE])
        avail.append(w.avail[:, :DEADLINE])
        rp = RegionalPredictor(
            w, lambda tr, r, s=s: NoisyPredictor(
                tr, KIND, LEVEL, seed=int(s) * 1009 + r)
        )
        preds.append(rp.matrix(fast_sim.W1MAX - 1)[:, :DEADLINE])
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs(jobs_cfg), PAPER_TPUT,
        np.stack(prices).astype(np.float32),
        np.stack(avail).astype(np.int64),
        np.stack(preds).astype(np.float32),
        delta_mig=DELTA_MIG,
    )
    return numpy_eg_loop(jobs_cfg, out["utility"])


def _update_bench_json(rows, extra):
    """Fold the region_e2e rows into BENCH_pool_sim.json without disturbing
    the other modules' rows (shared merge in benchmarks.common)."""
    merge_bench_rows(_JSON_PATH, "region_e2e", "region_e2e", rows, extra)


def run():
    from repro.core import engine, fast_sim, selector
    from repro.core.policy_pool import region_pool, specs_to_arrays
    from repro.launch.mesh import make_pool_mesh, parse_pool_mesh_shape

    pool = region_pool()
    arrs = specs_to_arrays(pool)
    n_pol = len(pool)
    mesh = make_pool_mesh(
        shape=parse_pool_mesh_shape(os.environ.get("POOL_SIM_MESH", ""))
    )
    market, jobs, t0s, seeds = _workload()
    jobs_cfg = fast_sim.unstack_jobs(jobs)
    units = DEADLINE * n_pol * N_JOBS * N_REGIONS

    prep = lambda backend, lo=0, hi=N_JOBS: engine.prepare_noisy_inputs_regions(
        market, t0s[lo:hi], DEADLINE, KIND, LEVEL, seeds[lo:hi],
        prep_backend=backend,
    )
    engine_run = lambda: engine.simulate_and_select(
        arrs, jobs, PAPER_TPUT, None, None, None,
        mesh=mesh, delta_mig=DELTA_MIG, job_chunk=CHUNK,
        prep=lambda lo, hi: prep("numpy", lo, hi),
    )

    res, total_secs = _timeit(engine_run)
    _, prep_np_secs = _timeit(lambda: prep("numpy"))
    _, prep_jax_secs = _timeit(
        lambda: jax.block_until_ready(prep("jax")[2])
    )

    # --- the replaced host-loop pipeline, same draws, measured whole ---
    st_loop, loop_secs = _timeit(
        lambda: _loop_pipeline(market, jobs_cfg, t0s, seeds, arrs),
        repeat=1,
    )

    rows = [
        ("region_e2e_total", total_secs * 1e6, units / total_secs),
        ("region_e2e_loop", loop_secs * 1e6, units / loop_secs),
        ("region_e2e_prep_numpy", prep_np_secs * 1e6, units / prep_np_secs),
        ("region_e2e_prep_jax", prep_jax_secs * 1e6, units / prep_jax_secs),
    ]
    ratio = loop_secs / total_secs
    rows.append(("region_e2e_engine_vs_loop", 0.0, ratio))
    # identical forecast draws + the shared EG update rule: both pipelines
    # must land on the same winning lane (f32 vs f64 EG)
    same = float(res.best_policy() == selector.best_policy(st_loop))
    rows.append(("region_e2e_same_winner", 0.0, same))

    _update_bench_json(rows, {
        "workload": {
            "jobs": N_JOBS, "slots": DEADLINE, "regions": N_REGIONS,
            "policies": n_pol, "delta_mig": DELTA_MIG,
            "job_chunk": CHUNK, "noise": f"{KIND}@{LEVEL:g}",
            "pool": "region_pool(36)",
        },
        "pool_mesh": "x".join(map(str, mesh.devices.shape)),
        "engine_vs_loop": ratio,
        "prep_jax_vs_numpy": prep_np_secs / prep_jax_secs,
        "winner": pool[res.best_policy()].name,
    })
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows

    print_rows(run())
