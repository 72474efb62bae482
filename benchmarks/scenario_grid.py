"""Scenario-matrix evaluation harness: the full 124-lane pool swept over a
parameterized regime grid, with the regime axis batched through the sharded
simulator instead of looped on host.

The paper's headline claim (Fig. 9/10) is that online selection *adapts*
across market regimes — but the repo's benches measure four hand-picked
regimes. This module turns the claim into a measured winner map over
dozens of market worlds:

  axes      availability level (``avail_mean``) x price volatility
            (``price_sigma``) x deadline tightness (workload scale; the
            deadline stays 10 slots so market tensors stay uniform) x
            restart overhead (``mu1:mu2`` reconfiguration penalties) x
            prediction noise level — 3 x 2 x 2 x 2 x 2 = 48 regimes by
            default (the FrontierCS ``cant_be_late`` sets sweep three of
            these; this grid adds volatility and noise).
  batching  every regime contributes ``SCENARIO_GRID_JOBS`` jobs; regimes
            stack regime-major onto the jobs axis (one vectorized market
            generator — data.synthetic.market_regime_batch — one
            concatenated trace for the batched window gather, one
            noisy_matrix_batch call with per-row noise levels, one
            fast_sim.concat_jobs job stack). ``core.engine.
            simulate_and_select`` then runs the whole stack through
            ``simulate_pool_jobs_sharded`` with ``job_chunk`` streaming.
            The ONE exception to "no host loop": ``tput`` is a static jit
            argument, so the restart-overhead axis cannot ride the jobs
            axis — regimes are mu-major and the sweep issues one batched
            call per distinct throughput config (2 calls for 48 regimes),
            each covering its whole contiguous regime block.
  output    per-regime winner map (argmax lane of the per-regime mean
            utility) + regret table: the globally-best fixed lane's
            per-regime regret vs the per-regime oracle-best, and the EG
            selector's per-regime regret ratio (Thm. 2 bound). Folded into
            BENCH_pool_sim.json via the merge-preserve pattern;
            ``SCENARIO_GRID_JSON`` additionally writes a standalone
            winner-map artifact (the CI upload).

Env knobs: SCENARIO_GRID_JOBS (jobs per regime, default 16),
SCENARIO_GRID_AVAIL / SCENARIO_GRID_SIGMA / SCENARIO_GRID_TIGHT /
SCENARIO_GRID_NOISE (comma-separated values per axis), SCENARIO_GRID_MU
(comma-separated ``mu1:mu2`` pairs), SCENARIO_GRID_CHUNK (job_chunk for
the streamed simulation, 0 = one shot), SCENARIO_GRID_REPEAT,
SCENARIO_GRID_JSON, SCENARIO_GRID_TELEMETRY (path: run an untimed
``collect=True`` flight-recorder pass, write the per-regime telemetry
ledger there, and pin it bitwise against the timed pass's utilities);
POOL_SIM_MESH / POOL_SIM_JSON as everywhere else.

tests/test_scenario_grid.py pins one batched-grid cell bitwise against an
independent single-regime ``simulate_pool_jobs`` run, seed-determinism of
the grid, and directional sanity across axes; tests/test_bench_regression
pins the per-regime winner map under RUN_BENCH_REGRESSION=1.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import numpy as np

from benchmarks.common import PAPER_TPUT, job_stream_arrays, merge_bench_rows
from benchmarks.pool_sim_bench import _JSON_PATH


def _floats(env: str, default: str) -> Tuple[float, ...]:
    return tuple(float(x) for x in os.environ.get(env, default).split(",") if x)


def _mu_pairs(env: str, default: str) -> Tuple[Tuple[float, float], ...]:
    out = []
    for tok in os.environ.get(env, default).split(","):
        if not tok:
            continue
        m1, m2 = tok.split(":")
        out.append((float(m1), float(m2)))
    return tuple(out)


N_JOBS = int(os.environ.get("SCENARIO_GRID_JOBS", "16"))
CHUNK = int(os.environ.get("SCENARIO_GRID_CHUNK", "0"))
REPEAT = int(os.environ.get("SCENARIO_GRID_REPEAT", "1"))
AVAIL_AXIS = _floats("SCENARIO_GRID_AVAIL", "3.5,5.5,9.0")
SIGMA_AXIS = _floats("SCENARIO_GRID_SIGMA", "0.25,0.5")
TIGHT_AXIS = _floats("SCENARIO_GRID_TIGHT", "0.8,1.15")
MU_AXIS = _mu_pairs("SCENARIO_GRID_MU", "0.9:0.95,0.7:0.85")
NOISE_AXIS = _floats("SCENARIO_GRID_NOISE", "0.0,0.3")
GRID_JSON = os.environ.get("SCENARIO_GRID_JSON", "")
# non-empty: run a second collect=True pass (outside the timed sweep, so
# timings stay clean), write the per-regime telemetry ledger here, and pin
# it bitwise against the timed pass's utilities
TELEMETRY_JSON = os.environ.get("SCENARIO_GRID_TELEMETRY", "")

# every regime shares the market seed (so e.g. the availability axis is a
# pointwise-comparable paired draw) and paper_market's scarce-regime price
# level / diurnal swing; days=4 gives 192 slots of t0 room per regime
MARKET_SEED = 11
GRID_DAYS = 4.0
JOB_SEED = 7
DEADLINE = 10
NOISE_KIND = "fixed_uniform"
MEAN_PRICE = 0.7
AVAIL_SEASON_AMP = 3.0


@dataclass(frozen=True)
class Regime:
    avail_mean: float
    price_sigma: float
    tight: float          # workload scale (deadline tightness)
    mu1: float
    mu2: float
    noise: float          # prediction noise level (fixed_uniform)

    @property
    def key(self) -> str:
        return (f"a{self.avail_mean:g}_s{self.price_sigma:g}"
                f"_t{self.tight:g}_m{self.mu1:g}_n{self.noise:g}")


def grid_regimes(
    avail: Sequence[float] = AVAIL_AXIS,
    sigma: Sequence[float] = SIGMA_AXIS,
    tight: Sequence[float] = TIGHT_AXIS,
    mu: Sequence[Tuple[float, float]] = MU_AXIS,
    noise: Sequence[float] = NOISE_AXIS,
) -> List[Regime]:
    """The full cartesian grid, mu-major: the throughput axis varies
    slowest so each distinct (mu1, mu2) is one contiguous regime block —
    what lets evaluate_grid run one batched call per throughput config."""
    return [
        Regime(a, s, t, m1, m2, nz)
        for (m1, m2) in mu
        for a in avail
        for s in sigma
        for t in tight
        for nz in noise
    ]


def build_grid_inputs(regimes: List[Regime], n_jobs: int = N_JOBS,
                      deadline: int = DEADLINE):
    """Regime-major stacked engine inputs for the whole grid.

    One vectorized market generation (R regimes in one
    ``market_regime_batch`` call), one concatenated trace so the batched
    window gather + noisy forecast stack run as ONE
    ``engine.prepare_noisy_inputs`` call (per-regime noise levels ride the
    per-row ``level`` axis), and one ``concat_jobs`` stack of per-regime
    job blocks. Base job draws, window starts and noise seeds are shared
    across regimes — regimes are matched pairs, so axis comparisons are
    controlled — while each regime's workloads carry its tightness scale.

    Returns ``(jobs (R*K,), prices (R*K, d), avail (R*K, d), preds
    (R*K, d, W1MAX, 2), t0s (K,))``.
    """
    from repro.core import engine, fast_sim
    from repro.core.market import from_arrays
    from repro.data.synthetic import market_regime_batch

    R = len(regimes)
    prices_r, avail_r = market_regime_batch(
        np.full(R, MARKET_SEED, np.int64),
        days=GRID_DAYS,
        mean_price=MEAN_PRICE,
        price_sigma=[r.price_sigma for r in regimes],
        avail_mean=[r.avail_mean for r in regimes],
        avail_season_amp=AVAIL_SEASON_AMP,
    )
    T = prices_r.shape[1]
    # windows never cross a regime boundary (t0 <= T - d - 1 within each
    # regime), so the concatenated trace + offset t0s reuse the engine's
    # batched prep verbatim
    cat = from_arrays(prices_r.reshape(-1), avail_r.reshape(-1))
    t0s = np.random.default_rng(JOB_SEED + 1).integers(
        0, T - deadline - 1, n_jobs
    )
    t0s_all = (np.arange(R)[:, None] * T + t0s[None, :]).reshape(-1)
    seeds = JOB_SEED * 100003 + np.arange(n_jobs)
    prices, avail, preds = engine.prepare_noisy_inputs(
        cat, t0s_all, deadline, NOISE_KIND,
        np.repeat([r.noise for r in regimes], n_jobs),
        np.tile(seeds, R),
    )
    jobs = fast_sim.concat_jobs([
        job_stream_arrays(np.random.default_rng(JOB_SEED), n_jobs, deadline,
                          workload_scale=r.tight)
        for r in regimes
    ])
    return jobs, prices, avail, preds, t0s


def evaluate_grid(pool_arrays: dict, regimes: List[Regime], jobs, prices,
                  avail, preds, n_jobs: int = N_JOBS, *,
                  job_chunk: int = CHUNK, mesh=None,
                  backend: str = "xla", collect: bool = False):
    """Run the stacked grid through the engine: one ``simulate_and_select``
    call per distinct throughput config (contiguous mu-major block), each
    covering every regime in the block on the jobs axis — no per-regime
    host loop over ``simulate_pool_jobs``. Returns (R, K, M) raw utilities
    in regime order; with ``collect=True``, ``(util, sim_out)`` where
    ``sim_out`` is the merged flight-recorder dict ((R*K, M, ...) leaves,
    regime-major) for ``obs.ledger.grid_ledger``."""
    from repro.configs.base import ThroughputConfig
    from repro.core import engine, fast_sim

    R = len(regimes)
    M = int(np.asarray(pool_arrays["kind"]).shape[0])
    util = np.empty((R, n_jobs, M), np.float32)
    sim_chunks = []
    lo = 0
    while lo < R:
        hi = lo + 1
        while hi < R and (regimes[hi].mu1, regimes[hi].mu2) == (
                regimes[lo].mu1, regimes[lo].mu2):
            hi += 1
        tput = ThroughputConfig(alpha=PAPER_TPUT.alpha, beta=PAPER_TPUT.beta,
                                mu1=regimes[lo].mu1, mu2=regimes[lo].mu2)
        a, b = lo * n_jobs, hi * n_jobs
        res = engine.simulate_and_select(
            pool_arrays, fast_sim.slice_jobs(jobs, a, b), tput,
            prices[a:b], avail[a:b], preds[a:b],
            mesh=mesh, backend=backend, job_chunk=job_chunk,
            return_utilities=True, collect=collect,
        )
        util[lo:hi] = res.utilities.reshape(hi - lo, n_jobs, M)
        if collect:
            sim_chunks.append(res.sim_out)
        lo = hi
    if collect:
        sim_out = {k: (np.asarray(sim_chunks[0][k]) if len(sim_chunks) == 1
                       else np.concatenate(
                           [np.asarray(c[k]) for c in sim_chunks]))
                   for k in sim_chunks[0]}
        return util, sim_out
    return util


def analyze_grid(pool, regimes: List[Regime], util: np.ndarray, jobs) -> dict:
    """Winner map + regret table from the (R, K, M) utility tensor.

    Per regime: the winner lane (argmax of the per-regime mean utility),
    the oracle-best mean utility, the globally-best fixed lane's regret
    vs that oracle, and the EG selector's regret ratio (final regret over
    the Thm. 2 bound) from a per-regime selector run over the regime's
    K-job stream."""
    from repro.core import fast_sim, selector
    from repro.core.job import normalize_utility_batch

    R, K, M = util.shape
    mean_u = util.mean(axis=1)                      # (R, M)
    winner_idx = mean_u.argmax(axis=1)
    oracle = mean_u.max(axis=1)                     # per-regime oracle-best
    fixed_best = int(mean_u.mean(axis=0).argmax())  # best single lane overall
    regret_fixed = oracle - mean_u[:, fixed_best]
    per_regime = []
    for r, reg in enumerate(regimes):
        jb = fast_sim.slice_jobs(jobs, r * K, (r + 1) * K)
        st, _ = selector.run_eg_scan(
            selector.eg_init(M, K), normalize_utility_batch(jb, util[r])
        )
        per_regime.append({
            "key": reg.key,
            "avail_mean": reg.avail_mean, "price_sigma": reg.price_sigma,
            "tight": reg.tight, "mu1": reg.mu1, "mu2": reg.mu2,
            "noise": reg.noise,
            "winner": pool[int(winner_idx[r])].name,
            "winner_idx": int(winner_idx[r]),
            "best_mean_utility": float(oracle[r]),
            "fixed_lane_regret": float(regret_fixed[r]),
            "eg_regret_ratio": float(
                selector.regret(st) / selector.regret_bound(M, K)
            ),
            "eg_winner": pool[selector.best_policy(st)].name,
        })
    return {
        "mean_u": mean_u,
        "winner_idx": winner_idx,
        "fixed_best": fixed_best,
        "fixed_best_name": pool[fixed_best].name,
        "regret_fixed": regret_fixed,
        "per_regime": per_regime,
    }


def run():
    import jax

    from repro.core.policy_pool import (
        baseline_specs,
        paper_pool,
        rand_deadline_pool,
        specs_to_arrays,
    )
    from repro.launch.mesh import make_pool_mesh, parse_pool_mesh_shape

    pool = paper_pool() + rand_deadline_pool() + baseline_specs()
    arrs = specs_to_arrays(pool)
    regimes = grid_regimes()
    mesh = make_pool_mesh(
        shape=parse_pool_mesh_shape(os.environ.get("POOL_SIM_MESH", ""))
    )
    jobs, prices, avail, preds, _ = build_grid_inputs(regimes)

    ev = lambda: evaluate_grid(arrs, regimes, jobs, prices, avail, preds,
                               mesh=mesh)
    util = ev()                     # warm-up call pays compilation
    t0 = time.perf_counter()
    for _ in range(max(REPEAT, 1)):
        ev()
    secs = (time.perf_counter() - t0) / max(REPEAT, 1)

    res = analyze_grid(pool, regimes, util, jobs)
    eg_ratios = [p["eg_regret_ratio"] for p in res["per_regime"]]
    units = len(regimes) * util.shape[1] * len(pool) * DEADLINE
    rows = [
        ("scenario_grid_sweep", secs * 1e6, units / secs),
        ("scenario_grid_regimes", 0.0, float(len(regimes))),
        ("scenario_grid_winner_diversity", 0.0,
         float(len(set(res["winner_idx"].tolist())))),
        ("scenario_grid_regret_fixed_mean", 0.0,
         float(np.mean(res["regret_fixed"]))),
        ("scenario_grid_regret_fixed_max", 0.0,
         float(np.max(res["regret_fixed"]))),
        ("scenario_grid_eg_regret_ratio_mean", 0.0,
         float(np.mean(eg_ratios))),
    ]
    # per-regime winner rows: the regression pins read the lane INDEX off
    # the derived column (names live in the extra payload)
    rows += [
        (f"scenario_grid_winner__{p['key']}", 0.0, float(p["winner_idx"]))
        for p in res["per_regime"]
    ]

    telemetry = None
    if TELEMETRY_JSON:
        from repro.configs.base import ThroughputConfig
        from repro.obs import grid_ledger

        # flight-recorder pass OUTSIDE the timed sweep: collect=False above
        # keeps the timings on the exact shipped program, and the bitwise
        # self-check below proves the collect path didn't perturb it
        util_t, sim_out = evaluate_grid(
            arrs, regimes, jobs, prices, avail, preds, mesh=mesh,
            collect=True,
        )
        tputs = [ThroughputConfig(alpha=PAPER_TPUT.alpha,
                                  beta=PAPER_TPUT.beta,
                                  mu1=r.mu1, mu2=r.mu2)
                 for r in regimes]
        meta = [{"key": r.key, "avail_mean": r.avail_mean,
                 "price_sigma": r.price_sigma, "tight": r.tight,
                 "mu1": r.mu1, "mu2": r.mu2, "noise": r.noise}
                for r in regimes]
        telemetry = grid_ledger(meta, util_t, sim_out, jobs, tputs,
                                util.shape[1],
                                lane_names=[p.name for p in pool])
        bitwise = bool(np.array_equal(util, util_t))
        rows += [
            ("scenario_grid_tel_bitwise_match", 0.0, float(bitwise)),
            ("scenario_grid_tel_cost_residual", 0.0,
             telemetry["max_abs_cost_residual"]),
            ("scenario_grid_tel_utility_residual", 0.0,
             telemetry["max_abs_utility_residual"]),
        ]
        os.makedirs(os.path.dirname(TELEMETRY_JSON) or ".", exist_ok=True)
        with open(TELEMETRY_JSON, "w") as f:
            json.dump(telemetry, f, indent=2)

    extra = {
        "workload": {
            "regimes": len(regimes), "jobs_per_regime": util.shape[1],
            "slots": DEADLINE, "policies": len(pool),
            "noise_kind": NOISE_KIND, "days": GRID_DAYS,
            "pool": "paper_pool(112) + rand_deadline(9) + baselines(3)",
        },
        "axes": {
            "avail_mean": list(AVAIL_AXIS), "price_sigma": list(SIGMA_AXIS),
            "tight": list(TIGHT_AXIS),
            "mu": [f"{m1:g}:{m2:g}" for m1, m2 in MU_AXIS],
            "noise": list(NOISE_AXIS),
        },
        "pool_mesh": "x".join(map(str, mesh.devices.shape)),
        "job_chunk": CHUNK,
        "fixed_best": res["fixed_best_name"],
        "winner_map": {p["key"]: p["winner"] for p in res["per_regime"]},
        "per_regime": res["per_regime"],
        "devices": jax.device_count(),
    }
    merge_bench_rows(_JSON_PATH, "scenario_grid", "scenario_grid", rows,
                     extra)
    if GRID_JSON:
        os.makedirs(os.path.dirname(GRID_JSON) or ".", exist_ok=True)
        with open(GRID_JSON, "w") as f:
            json.dump(extra, f, indent=2)
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows

    print_rows(run())
