"""Pool-simulator throughput: the repo's perf trajectory for the hottest path.

Measures slots * policies * jobs / sec over the paper's mixed workload
(112-policy pool + 9 RAND_DEADLINE + 3 baselines, Fig. 9 job distribution):

  partitioned  fast_sim.simulate_pool_jobs: AHAP lanes on the DP-bearing
               scan with ONE batched (P_ahap, w1, tn+1) window DP per slot,
               cheap kinds (AHANP/OD/MSU/UP/RAND_DEADLINE) on the DP-free
               scan, scattered back to pool order.
  pallas       the partitioned path with the fused Pallas window-DP kernel —
               one kernel launch per scan slot for the whole lane batch
               (interpret mode on CPU, compiled on TPU).
  sharded      fast_sim.simulate_pool_jobs_sharded over the POOL_SIM_MESH
               pool mesh (default: 1-D jobs mesh over every visible device;
               identical to `partitioned` when one device is visible; force
               more with XLA_FLAGS=--xla_force_host_platform_device_count=N).
  sharded_lanes / sharded_2d   (multi-device only) the lanes-only
               (1, n_dev) and balanced 2-D (a, b) pool meshes — the lane
               axis is the parallelism frontier for small-jobs/huge-pool
               workloads.

`*_scale` rows rerun the XLA paths at the paper's Fig. 9/10 job counts
(1000s of jobs; POOL_SIM_SCALE_JOBS to override).
`pool_sim_sharded_scale_vs_partitioned` is the
multi-device scale ratio (partitioned_scale secs / sharded_scale secs,
>= 1.0 means sharding pays for itself at Fig. 9/10 scale) — the guard's
multi-device half pins it.

Env knobs: POOL_SIM_JOBS, POOL_SIM_REPEAT, POOL_SIM_SCALE_JOBS,
POOL_SIM_SCALE_REPEAT (0 skips the scale rows), POOL_SIM_MESH ("4", "2x2",
"1x4", ... — the mesh shape for the sharded rows; "auto"/unset = 1-D over
all devices), POOL_SIM_JSON (redirect the JSON artifact — the regression
guard uses this so its shrunken config never clobbers the tracked
BENCH_pool_sim.json).

Writes BENCH_pool_sim.json (machine-readable rows) so successive runs on
one host can be compared; also returned as benchmark rows for run.py. The
rows are timings of whatever backend runs them (XLA:CPU in CI); the chip's
numbers are the benchmark's (bench/, PERF_LEDGER.jsonl).
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from benchmarks.common import PAPER_JOB, PAPER_TPUT, Row, job_stream, paper_market

N_JOBS = int(os.environ.get("POOL_SIM_JOBS", "8"))
SCALE_JOBS = int(os.environ.get("POOL_SIM_SCALE_JOBS", "1000"))
DEADLINE = 10
REPEAT = int(os.environ.get("POOL_SIM_REPEAT", "5"))
SCALE_REPEAT = int(os.environ.get("POOL_SIM_SCALE_REPEAT", "2"))

_JSON_PATH = os.environ.get(
    "POOL_SIM_JSON",
    os.path.join(os.path.dirname(os.path.dirname(__file__)),
                 "BENCH_pool_sim.json"),
)


def _workload(n_jobs: int):
    """Fig. 9-style workload: random jobs on random market windows."""
    from repro.core import fast_sim
    from repro.core.predictor import NoisyPredictor

    rng = np.random.default_rng(7)
    jobs = list(job_stream(rng, n_jobs, deadline=DEADLINE))
    market = paper_market(seed=13, days=4)
    traces = [
        market.window(int(rng.integers(0, len(market) - DEADLINE - 1)), DEADLINE + 1)
        for _ in range(n_jobs)
    ]
    prices = np.stack([t.prices[:DEADLINE] for t in traces]).astype(np.float32)
    avail = np.stack([t.avail[:DEADLINE] for t in traces]).astype(np.int64)
    preds = np.stack([
        NoisyPredictor(t, "fixed_uniform", 0.2, seed=i).matrix(
            fast_sim.W1MAX - 1
        )[:DEADLINE]
        for i, t in enumerate(traces)
    ]).astype(np.float32)
    return jobs, prices, avail, preds


def _bench(fn, repeat: int = REPEAT) -> float:
    """Seconds per call at steady state (first call pays compilation)."""
    jax.block_until_ready(fn()["utility"])
    t0 = time.perf_counter()
    for _ in range(repeat):
        jax.block_until_ready(fn()["utility"])
    return (time.perf_counter() - t0) / repeat


def _balanced_2d(n_dev: int):
    """Largest (a, b) factorization of n_dev with a <= b and a > 1, or None
    (prime / single device — the lanes-only mesh already covers it)."""
    for a in range(int(n_dev ** 0.5), 1, -1):
        if n_dev % a == 0:
            return (a, n_dev // a)
    return None


def run():
    from repro.core import fast_sim
    from repro.core.policy_pool import (
        baseline_specs,
        paper_pool,
        rand_deadline_pool,
        specs_to_arrays,
    )
    from repro.launch.mesh import make_pool_mesh, parse_pool_mesh_shape

    # 112 + 9 + 3: mixed AHAP/AHANP/RAND_DEADLINE/baseline
    pool = paper_pool() + rand_deadline_pool() + baseline_specs()
    arrs = specs_to_arrays(pool)
    jobs, prices, avail, preds = _workload(N_JOBS)
    stacked = fast_sim.stack_jobs(jobs)
    n_pol = len(pool)
    n_dev = jax.device_count()
    work_units = DEADLINE * n_pol * N_JOBS   # slots * policies * jobs per call

    on_tpu = jax.default_backend() == "tpu"
    pallas_backend = "pallas" if on_tpu else "pallas-interpret"
    mesh_shape = parse_pool_mesh_shape(os.environ.get("POOL_SIM_MESH", ""))
    pool_mesh = make_pool_mesh(shape=mesh_shape)

    paths = {
        "partitioned": lambda: fast_sim.simulate_pool_jobs(
            arrs, stacked, PAPER_TPUT, prices, avail, preds, backend="xla"
        ),
        "pallas": lambda: fast_sim.simulate_pool_jobs(
            arrs, stacked, PAPER_TPUT, prices, avail, preds,
            backend=pallas_backend,
        ),
        "sharded": lambda: fast_sim.simulate_pool_jobs_sharded(
            arrs, stacked, PAPER_TPUT, prices, avail, preds, backend="xla",
            mesh=pool_mesh,
        ),
    }
    if n_dev > 1:
        # the lane-axis frontier: all devices on lanes, and the balanced 2-D
        # grid when the device count factors
        lane_mesh = make_pool_mesh(shape=(1, n_dev))
        paths["sharded_lanes"] = lambda: fast_sim.simulate_pool_jobs_sharded(
            arrs, stacked, PAPER_TPUT, prices, avail, preds, backend="xla",
            mesh=lane_mesh,
        )
        shape_2d = _balanced_2d(n_dev)
        if shape_2d:
            mesh_2d = make_pool_mesh(shape=shape_2d)
            paths["sharded_2d"] = lambda: fast_sim.simulate_pool_jobs_sharded(
                arrs, stacked, PAPER_TPUT, prices, avail, preds,
                backend="xla", mesh=mesh_2d,
            )

    secs, rows = {}, []
    for name, fn in paths.items():
        secs[name] = _bench(fn)
        rate = work_units / secs[name]
        rows.append((f"pool_sim_{name}", secs[name] * 1e6, rate))

    # Fig. 9/10-scale workload (1000s of jobs): XLA paths only — the
    # interpreter would take far longer.
    scale_secs = {}
    if SCALE_REPEAT > 0 and SCALE_JOBS > 0:
        s_jobs, s_prices, s_avail, s_preds = _workload(SCALE_JOBS)
        s_stacked = fast_sim.stack_jobs(s_jobs)
        scale_units = DEADLINE * n_pol * SCALE_JOBS
        scale_paths = {
            "partitioned_scale": lambda: fast_sim.simulate_pool_jobs(
                arrs, s_stacked, PAPER_TPUT, s_prices, s_avail, s_preds,
                backend="xla",
            ),
            "sharded_scale": lambda: fast_sim.simulate_pool_jobs_sharded(
                arrs, s_stacked, PAPER_TPUT, s_prices, s_avail, s_preds,
                backend="xla", mesh=pool_mesh,
            ),
        }
        for name, fn in scale_paths.items():
            scale_secs[name] = _bench(fn, repeat=SCALE_REPEAT)
            rows.append((
                f"pool_sim_{name}", scale_secs[name] * 1e6,
                scale_units / scale_secs[name],
            ))
        # >= 1.0 means the sharded path is no slower than single-device
        # partitioned at Fig. 9/10 scale (trivially ~1.0 on one device,
        # where sharded falls back to the partitioned path)
        rows.append((
            "pool_sim_sharded_scale_vs_partitioned", 0.0,
            scale_secs["partitioned_scale"] / scale_secs["sharded_scale"],
        ))

    payload = {
        "workload": {
            "policies": n_pol, "jobs": N_JOBS, "slots": DEADLINE,
            "scale_jobs": SCALE_JOBS if scale_secs else 0,
            "pool": "paper_pool(112) + rand_deadline(9) + baselines(3)",
        },
        "backend": jax.default_backend(),
        "devices": n_dev,
        "pool_mesh": "x".join(map(str, pool_mesh.devices.shape)),
        "pallas_mode": pallas_backend,
        "rows": [
            {"name": n, "us_per_call": us, "derived": d} for n, us, d in rows
        ],
    }
    # benchmarks/{region_sim,selection_e2e,fleet_sim,scenario_grid}.py merge
    # their rows into the same file in place; a pool_sim rerun must carry
    # them over, not clobber them
    try:
        with open(_JSON_PATH) as f:
            prev = json.load(f)
    except (OSError, json.JSONDecodeError):
        prev = {}
    payload["rows"] += [
        r for r in prev.get("rows", [])
        if str(r.get("name", "")).startswith(
            ("region_sim", "selection_e2e", "fleet_sim", "scenario_grid"))
    ]
    for key in ("region", "selection", "fleet", "scenario_grid"):
        if key in prev:
            payload[key] = prev[key]
    with open(_JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows

    print_rows(run())
