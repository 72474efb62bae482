"""Shared benchmark utilities. Every benchmark returns rows of
(name, us_per_call, derived) — us_per_call is the wall-time of the dominant
computation, derived is the figure's headline number."""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Tuple

import numpy as np

from repro.configs.base import JobConfig, ThroughputConfig

Row = Tuple[str, float, float]

# the paper's evaluation setting (Sec. VI-A): LLaMA2-7B LoRA job, 30-min
# slots, workload 80 over deadline 10, N in [1, 12], mu = 0.9
PAPER_JOB = JobConfig(workload=80.0, deadline=10, n_min=1, n_max=12,
                      value=120.0, gamma=2.0, on_demand_price=1.0)
PAPER_TPUT = ThroughputConfig(alpha=1.0, beta=0.0, mu1=0.9, mu2=0.95)


def _block(x) -> None:
    """Recursively block until every jax array inside ``x`` is ready.
    Duck-typed (``block_until_ready``) so numpy/python leaves are free and
    no jax import is needed; descends dicts, sequences, NamedTuples and
    dataclasses (SelectionResult, EGState, result dicts...)."""
    if x is None:
        return
    if hasattr(x, "block_until_ready"):
        x.block_until_ready()
    elif isinstance(x, dict):
        for v in x.values():
            _block(v)
    elif isinstance(x, (list, tuple)):
        for v in x:
            _block(v)
    elif dataclasses.is_dataclass(x) and not isinstance(x, type):
        for f in dataclasses.fields(x):
            _block(getattr(x, f.name))


def timed(fn: Callable, *args, repeat: int = 1, block: bool = True, **kw):
    """Wall-time ``fn(*args, **kw)`` averaged over ``repeat`` calls.

    ``block=True`` (the default) blocks on every jax array reachable from
    the return value INSIDE the timed region — jax dispatch is async, so
    without it a benchmark measures enqueue time, not compute time.
    ``block=False`` restores the raw dispatch measurement."""
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
        if block:
            _block(out)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6  # us


def job_stream_arrays(rng: np.random.Generator, n: int, deadline: int = 10,
                      workload_scale: float = 1.0):
    """Fig. 9 job distribution as stacked fast_sim.JobArrays — ONE vectorized
    rng call per field (the engine-scale path; no per-job python loop).
    L ~ U[70,120], Nmin in [1,4), Nmax in [12,17); value/gamma/on-demand
    price from the paper job. Leaf dtypes match fast_sim.stack_jobs, so
    ``stack_jobs(list(job_stream(rng, n)))`` equals
    ``job_stream_arrays(rng2, n)`` bitwise for equal rng states.

    ``workload_scale`` multiplies the drawn workloads (in f64, before the
    f32 cast) — the scenario grid's deadline-tightness axis: the deadline
    stays 10 slots so market tensors stay uniform, while the same base
    draws get proportionally more or less work. 1.0 is a bitwise no-op."""
    from repro.core.fast_sim import JobArrays

    cfg = JobConfig(deadline=deadline, value=PAPER_JOB.value)
    return JobArrays(
        workload=(rng.uniform(70, 120, n) * workload_scale).astype(np.float32),
        deadline=np.full(n, cfg.deadline, np.int32),
        n_min=rng.integers(1, 4, n).astype(np.int32),
        n_max=rng.integers(12, 17, n).astype(np.int32),
        value=np.full(n, cfg.value, np.float32),
        gamma=np.full(n, cfg.gamma, np.float32),
        p_o=np.full(n, cfg.on_demand_price, np.float32),
    )


def job_stream(rng: np.random.Generator, n: int, deadline: int = 10):
    """Fig. 9 job distribution as JobConfig rows — delegates to
    :func:`job_stream_arrays` so figure scripts and the engine benchmarks
    draw identical jobs from equal rng states (note: the delegation draws
    each field in one vectorized call, so the stream consumption differs
    from the pre-engine per-job loop)."""
    arrs = job_stream_arrays(rng, n, deadline)
    for k in range(n):
        yield JobConfig(
            workload=float(arrs.workload[k]),
            deadline=int(arrs.deadline[k]),
            n_min=int(arrs.n_min[k]),
            n_max=int(arrs.n_max[k]),
            value=float(arrs.value[k]),
            gamma=float(arrs.gamma[k]),
            on_demand_price=float(arrs.p_o[k]),
        )


def print_rows(rows: List[Row]):
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.6g}")


def merge_bench_rows(json_path: str, prefix: str, key: str, rows: List[Row],
                     extra: dict) -> None:
    """Fold one module's rows into a shared BENCH json in place: rows whose
    name starts with ``prefix`` are replaced, everything else is untouched,
    and the module's non-row extras live under the single top-level ``key``
    (so pool_sim_bench's full rewrite has one thing per module to carry
    over). Shared by region_sim and selection_e2e."""
    import json

    try:
        with open(json_path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError):
        payload = {"rows": []}
    payload["rows"] = [
        r for r in payload.get("rows", [])
        if not str(r.get("name", "")).startswith(prefix)
    ] + [{"name": n, "us_per_call": us, "derived": d} for n, us, d in rows]
    payload[key] = extra
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)


def numpy_eg_loop(jobs_cfg, utilities):
    """The host reference selection: per-job ``normalize_utility`` and one
    numpy ``selector.update`` per row of the (K, M) raw-utility matrix.
    Returns the final numpy SelectorState."""
    from repro.core import selector
    from repro.core.job import normalize_utility

    u = np.asarray(utilities)
    st = selector.init_selector(u.shape[1], len(jobs_cfg))
    for job, row in zip(jobs_cfg, u):
        st = selector.update(st, np.asarray(normalize_utility(job, row)))
    return st


# ---------------------------------------------------------------------------
# Shared policy-evaluation harness for the Fig. 5-8 sweeps
# ---------------------------------------------------------------------------

EVAL_SPEC_NAMES = ("ahap", "ahanp", "od_only", "msu", "up")


def eval_specs():
    """Representative AHAP/AHANP + the three baselines (paper Fig. 5-8)."""
    from repro.core.policy_pool import (
        KIND_AHANP,
        KIND_AHAP,
        PolicySpec,
        baseline_specs,
    )

    return [
        PolicySpec(KIND_AHAP, omega=3, v=1, sigma=0.7),
        PolicySpec(KIND_AHANP, sigma=0.7),
    ] + baseline_specs()


def best_of_family_utilities(jobs, traces, tput, **kw):
    """Paper methodology: 'the selected optimal policy is always the better
    of the two' — evaluate the whole 112-policy pool and report
    (best_ahap, best_ahanp, od, msu, up) mean utilities."""
    from repro.core.policy_pool import baseline_specs, paper_pool

    pool = paper_pool()
    specs = pool + baseline_specs()
    u = mean_utilities(jobs, traces, tput, specs=specs, **kw)
    ahap_u = max(u[i] for i, s in enumerate(pool) if s.kind == 0)
    ahanp_u = max(u[i] for i, s in enumerate(pool) if s.kind == 1)
    return np.array([ahap_u, ahanp_u, u[-3], u[-2], u[-1]])


def mean_utilities(
    jobs,
    traces,
    tput,
    noise_kind: str = "fixed_uniform",
    noise_level: float = 0.10,
    specs=None,
) -> np.ndarray:
    """(P,) mean utility of each spec over the (job, trace) pairs."""
    from repro.core import fast_sim
    from repro.core.policy_pool import specs_to_arrays
    from repro.core.predictor import NoisyPredictor, PerfectPredictor

    specs = specs or eval_specs()
    arrs = specs_to_arrays(specs)
    d = jobs[0].deadline
    assert all(j.deadline == d for j in jobs)
    prices = np.stack([t.prices[:d] for t in traces])
    avail = np.stack([t.avail[:d] for t in traces])
    preds = []
    for i, t in enumerate(traces):
        if noise_level <= 0:
            m = PerfectPredictor(t).matrix(fast_sim.W1MAX - 1)
        else:
            m = NoisyPredictor(t, noise_kind, noise_level, seed=i).matrix(
                fast_sim.W1MAX - 1
            )
        preds.append(m[:d])
    out = fast_sim.simulate_pool_jobs(
        arrs, fast_sim.stack_jobs(jobs), tput,
        np.asarray(prices, np.float32), np.asarray(avail, np.int64),
        np.asarray(np.stack(preds), np.float32),
    )
    return np.asarray(out["utility"]).mean(axis=0)


def paper_market(seed: int = 11, days: float = 30, **overrides):
    """The evaluation market regime: scarce availability with a strong
    diurnal cycle and volatile prices that regularly approach the on-demand
    rate — the conditions under which prediction pays (paper Sec. VI).
    Under abundant cheap spot, MSU is near-optimal and the paper's gaps
    vanish (EXPERIMENTS.md notes this sensitivity)."""
    from repro.core.market import vast_like_trace

    kw = dict(mean_price=0.7, price_sigma=0.5, avail_mean=5.5,
              avail_season_amp=3.0)
    kw.update(overrides)
    return vast_like_trace(seed=seed, days=days, **kw)


def windows(trace, n, deadline, rng):
    return [
        trace.window(int(rng.integers(0, len(trace) - deadline - 1)), deadline + 1)
        for _ in range(n)
    ]
