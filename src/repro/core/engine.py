"""Device-resident online selection engine: the paper's Algorithm 2 loop
(simulate every pool policy on the incoming job -> normalize utilities ->
EG update) end to end, with the (K, M) utility matrix never round-tripping
through host numpy.

Before this module the selection stage was the last fully host-bound part
of the pipeline: Fig. 9/10 built ``NoisyPredictor`` matrices one job at a
time, called ``normalize_utility`` in a per-job loop and ran
``selector.update`` as a numpy loop over 1000 jobs — while the simulator
underneath was jitted, kind-partitioned and 2-D sharded. The engine chains

  prep      batched trace-window gather (market.gather_windows) + ONE
            vectorized forecast stack (predictor.noisy_matrix_batch) —
            host numpy, array code instead of K python constructions; from
            predictor.BATCHED_SEEDING_MIN_ROWS jobs up even the K noise
            generators are seeded in one batched pass, leaving only numpy's
            own draw per row
  simulate  fast_sim.simulate_pool_jobs[_sharded] (jobs x lanes over the
            pool mesh)
  select    job.normalize_utility_batch + selector.run_eg_scan, fused into
            one jitted call — the (K, M) matrix stays a device array from
            the simulator's output to the selector's weight trajectories

and streams the job axis in chunks (``job_chunk``) when K is too large for
one resident (K, M, ...) simulation — the EG scan's state threads through
the chunks, so chunked and unchunked runs agree (the scan trajectories
bitwise, the mean-utility accumulator to f32 tolerance;
tests/test_selection_engine.py pins both).

Benchmarks: benchmarks/selection_e2e.py records the prep/simulate/select
split and pins the engine against the pre-engine host-loop pipeline
(``SEL_E2E_JOBS`` knob, rows in BENCH_pool_sim.json).

Tracing: each layer boundary is a ``jax.profiler.TraceAnnotation`` named
``sched.*`` (``sched.prep`` with ``.windows`` and ``.forecast``;
``sched.select`` around ``sched.stage``, the pool's ``sched.pool.*``,
``sched.eg`` and ``sched.fetch``), recorded only while a profiler capture
runs; ``repro.obs.counters`` counts the call's host-device transfers and
device launches.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ThroughputConfig
from repro.core import fast_sim, selector
from repro.core.job import normalize_utility_batch
from repro.core.market import gather_windows, require_finite
from repro.core.predictor import (
    noisy_matrix_batch,
    noisy_matrix_batch_jax,
    regional_noisy_matrix_jax,
)
from repro.obs import counters


def prepare_noisy_inputs(trace, t0s, deadline: int, kind: str, level,
                         seeds, horizon: Optional[int] = None,
                         avail_max: int = 16, prep_backend: str = "numpy"):
    """Batched Fig. 9-style prep: gather the K job windows in one indexing
    pass and emit the whole noisy forecast stack in one vectorized call.
    Returns ``(prices (K, d) f32, avail (K, d) i64, preds (K, d, W1MAX, 2)
    f32)`` ready for ``simulate_pool_jobs[_sharded]``. Row k equals the
    per-job ``NoisyPredictor(trace.window(t0s[k], d+1), ..., seed=seeds[k])``
    construction it replaces. ``level`` may be a scalar or a per-row (K,)
    array (``noisy_matrix_batch``'s contract) — the scenario grid passes
    per-regime noise levels through one call this way.

    ``prep_backend="jax"`` swaps the forecast construction for the jitted
    batched-PRNG ``noisy_matrix_batch_jax`` — ``preds`` comes back as a
    device array (born where the simulator consumes it; no host round-trip
    and no per-seed generator loop). The draws use JAX's counter-based
    PRNG, so the stacks are distribution- but not bitwise-equal to the
    numpy oracle (winner/regret parity pinned in
    tests/test_region_engine.py)."""
    horizon = fast_sim.W1MAX - 1 if horizon is None else horizon
    with TraceAnnotation("sched.prep", jobs=int(np.size(t0s))):
        with TraceAnnotation("sched.prep.windows"):
            pw, aw = gather_windows(trace, t0s, deadline + 1)
        with TraceAnnotation("sched.prep.forecast"):
            if prep_backend == "jax":
                preds = noisy_matrix_batch_jax(pw, aw, kind, level, seeds,
                                               horizon,
                                               avail_max)[:, :deadline]
            else:
                preds = noisy_matrix_batch(pw, aw, kind, level, seeds,
                                           horizon, avail_max)[:, :deadline]
                require_finite("forecast stack", preds)
                preds = preds.astype(np.float32)
            return (pw[:, :deadline].astype(np.float32),
                    aw[:, :deadline].astype(np.int64),
                    preds)


def prepare_noisy_inputs_regions(market, t0s, deadline: int, kind: str,
                                 level, seeds,
                                 horizon: Optional[int] = None,
                                 avail_max: int = 16,
                                 prep_backend: str = "numpy"):
    """Regional :func:`prepare_noisy_inputs`: gather every (job, region)
    market window of a :class:`RegionalMarket` and emit the full
    (K, R, d, W1MAX, 2) forecast stack in ONE batched pass over the
    flattened (K*R,) row axis. Returns ``(prices (K, R, d) f32, avail
    (K, R, d) i64, preds (K, R, d, W1MAX, 2) f32)`` ready for
    ``fast_sim.simulate_pool_jobs[_sharded]`` / the regional
    :func:`simulate_and_select` path.

    Row (k, r) is seeded ``seeds[k] * 1009 + r`` — the same
    decorrelate-regions-by-1009 convention as ``vast_like_regions`` — so
    the numpy path is bitwise-equal to stacking per-job
    ``RegionalPredictor(market.window(t0s[k], d+1), lambda tr, r:
    NoisyPredictor(tr, kind, level, seed=seeds[k] * 1009 + r))``
    constructions (the replaced Fig. 9/10 host loop).
    ``prep_backend="jax"`` builds the stack on device via
    ``regional_noisy_matrix_jax`` (different PRNG; winner/regret parity
    pinned, as for the single-region path)."""
    horizon = fast_sim.W1MAX - 1 if horizon is None else horizon
    n_regions = market.n_regions
    with TraceAnnotation("sched.prep", jobs=int(np.size(t0s))):
        with TraceAnnotation("sched.prep.windows"):
            pws, aws = zip(*(gather_windows(market.region(r), t0s,
                                            deadline + 1)
                             for r in range(n_regions)))
            pw = np.stack(pws, axis=1)                    # (K, R, d+1)
            aw = np.stack(aws, axis=1)
        with TraceAnnotation("sched.prep.forecast"):
            n_jobs = pw.shape[0]
            seeds = np.asarray(seeds)
            rseeds = (seeds[:, None] * np.int64(1009)
                      + np.arange(n_regions)[None, :])
            if prep_backend == "jax":
                preds = regional_noisy_matrix_jax(
                    pw, aw, kind, level, rseeds, horizon, avail_max
                )[:, :, :deadline]
            else:
                level_arr = np.asarray(level, float)
                lv = (np.repeat(level_arr, n_regions) if level_arr.ndim
                      else level_arr)
                preds = noisy_matrix_batch(
                    pw.reshape(n_jobs * n_regions, -1),
                    aw.reshape(n_jobs * n_regions, -1),
                    kind, lv, rseeds.reshape(-1), horizon, avail_max,
                ).reshape(n_jobs, n_regions, deadline + 1, horizon + 1, 2)
                preds = preds[:, :, :deadline]
                require_finite("forecast stack", preds)
                preds = preds.astype(np.float32)
            return (pw[:, :, :deadline].astype(np.float32),
                    aw[:, :, :deadline].astype(np.int64),
                    preds)


@functools.partial(jax.jit, static_argnames=("track_history", "collect"))
def _normalize_and_scan(jobs: fast_sim.JobArrays, u, state: selector.EGState,
                        track_history: bool, collect: bool = False):
    """The fused select stage: per-job [0,1] normalization of the (K, M)
    raw-utility matrix + the EG lax.scan, one device call."""
    un = normalize_utility_batch(jobs, u)
    return selector.run_eg_scan(state, un, track_history=track_history,
                                collect=collect)


def select_from_utilities(jobs: fast_sim.JobArrays, utilities,
                          state: selector.EGState,
                          track_history: bool = False,
                          collect: bool = False):
    """Public wrapper over the fused normalize+scan stage (the engine's
    'select' leg, also what benchmarks/selection_e2e.py times)."""
    return _normalize_and_scan(jobs, utilities, state, track_history, collect)


@dataclass
class SelectionResult:
    """Output of :func:`simulate_and_select`.

    ``state`` is the final EG selector state (pass it back in to continue
    the stream, e.g. Fig. 10's phase schedule); the trajectories are host
    numpy — (K,) scalars per job, plus the (K, M) post-update weight
    history when requested."""
    state: selector.EGState
    mean_utility: np.ndarray              # (M,) raw mean utility per policy
    max_weight: np.ndarray                # (K,) leader weight after each job
    regret: np.ndarray                    # (K,) cumulative regret after each job
    n_jobs: int
    weight_history: Optional[np.ndarray] = None   # (K, M), track_history only
    utilities: Optional[np.ndarray] = None        # (K, M), return_utilities only
    entropy: Optional[np.ndarray] = None          # (K,), collect only
    top_policy: Optional[np.ndarray] = None       # (K,) i32, collect only
    sim_out: Optional[dict] = None                # full sim dict, collect only

    def best_policy(self) -> int:
        return selector.best_policy(self.state)

    def iters_to_half(self) -> int:
        return selector.iters_to_half(self.max_weight)

    def regret_ratio(self) -> float:
        """Final regret over the Theorem 2 bound sqrt(2 K ln M)."""
        m = int(np.shape(self.state.weights)[0])
        return selector.regret(self.state) / selector.regret_bound(
            m, int(self.state.k)
        )

    def admission_rows(self, pool_arrays: dict, n: int, rng=None,
                       greedy: bool = False):
        """Per-job policy rows for fleet admission, drawn from the final
        EG weights — the select -> admit loop: ``core.fleet`` consumes the
        returned rows as each arriving job's policy. Returns ``(rows,
        idx)`` like :func:`fleet.policy_rows_from_weights`."""
        from repro.core import fleet  # deferred: fleet must not import engine

        return fleet.policy_rows_from_weights(
            pool_arrays, np.asarray(self.state.weights), n,
            rng=rng, greedy=greedy,
        )


def simulate_and_select(
    pool_arrays: dict,
    jobs: fast_sim.JobArrays,
    tput: ThroughputConfig,
    prices, avail, preds,
    *,
    backend: str = "xla",
    sharded: bool = True,
    mesh=None,
    eta: Optional[float] = None,
    state: Optional[selector.EGState] = None,
    job_chunk: int = 0,
    track_history: bool = False,
    return_utilities: bool = False,
    collect: bool = False,
    fallback=None,
    delta_mig: Optional[int] = None,
    p_od=None,
    prep=None,
) -> SelectionResult:
    """Run the whole online-selection workload in one call: sharded pool
    simulation of every (job, policy) cell, batched utility normalization,
    and the EG scan — Fig. 9's four-regime sweep is one call per regime.

    ``jobs`` are stacked (K,) JobArrays (benchmarks.common.job_stream_arrays
    or fast_sim.stack_jobs); ``prices``/``avail`` are (K, d) and ``preds``
    (K, d, W1MAX, 2) (see :func:`prepare_noisy_inputs`). ``sharded`` lays
    the (jobs x lanes) grid over ``mesh`` (default pool mesh; bitwise
    fallback to the single-device path on one device). ``state`` continues
    an earlier stream (defaults to a fresh uniform selector with Thm. 2's
    eta for K jobs); ``job_chunk`` > 0 streams the job axis in chunks of
    that size so K >> device memory works — equal-size chunks reuse the
    jitted partition runners' compilation cache.

    ``collect=True`` turns on the flight recorder end to end: the
    simulator emits its per-slot ``tel_*`` series (kept whole in
    ``sim_out``, chunk-concatenated along the job axis), and the EG scan
    adds per-job weight ``entropy`` and the ``top_policy`` leader trace.
    The flag is static and only ADDS scan outputs, so ``collect=False``
    runs the identical compiled program (pinned in
    tests/test_telemetry.py).

    ``fallback`` takes a ``repro.chaos.FallbackConfig`` to arm the
    prediction-failure monitor in the AHAP lanes (see
    ``repro.chaos.fallback``); ``None`` — the default — is the same
    static-flag discipline and compiles the identical shipped program
    (pinned in tests/test_chaos.py).

    **Regional mode** — (K, R, d) ``prices``/``avail`` and
    (K, R, d, W1MAX, 2) ``preds`` (:func:`prepare_noisy_inputs_regions`)
    select among region-aware lanes instead, and then need ``delta_mig``
    (the market's checkpoint-transfer cost; it and ``p_od`` are refused
    with flat inputs). The simulate leg runs the same entry point's
    region-aware scans, and the (K, M) utility matrix, region paths and
    migration counts stay device-resident between the sim, normalize and
    EG stages exactly as in the single-region path. ``p_od`` forwards the
    market's optional per-region on-demand multipliers. With R == 1 (and
    ``p_od=None``) the result is BITWISE-identical to the single-region
    engine on the squeezed inputs (pinned in tests/test_region_engine.py)
    — the per-cell programs agree bitwise and the select leg is shared
    code.

    ``prep`` optionally streams input construction: a callable
    ``prep(lo, hi) -> (prices, avail, preds)`` producing each chunk's
    inputs on demand (e.g. a :func:`prepare_noisy_inputs_regions` closure
    over the job windows), in which case the array arguments may be
    ``None``. The chunk loop DOUBLE-BUFFERS: chunk k's simulate/select
    work is dispatched asynchronously, then chunk k+1's prep runs on the
    host while the device chews — the prep leg hides behind the simulate
    leg instead of serializing with it. ``prep=None`` slices the passed
    arrays, which is the same values in the same order — results are
    unchanged."""
    n_jobs = int(np.shape(jobs.workload)[0])
    n_pol = int(np.asarray(pool_arrays["kind"]).shape[0])
    if state is None:
        state = selector.eg_init(n_pol, n_jobs, eta=eta)
    chunk = int(job_chunk) if job_chunk else n_jobs
    if chunk < 1:
        raise ValueError(f"job_chunk must be >= 1, got {job_chunk}")
    if prep is None and preds is None:
        raise ValueError("pass (prices, avail, preds) arrays or prep=")

    def _stage(lo, hi):
        with TraceAnnotation("sched.stage", lo=lo, hi=hi):
            if prep is not None:
                p, a, m = prep(lo, hi)
            else:
                p, a, m = prices[lo:hi], avail[lo:hi], preds[lo:hi]
                # slicing a device array short of its length runs a program
                counters.add("launches", sum(
                    isinstance(x, jax.Array) and hi - lo < x.shape[0]
                    for x in (prices, avail, preds)))
            # jnp.asarray starts the host->device transfer right away, so a
            # staged chunk is already in flight when its sim dispatches
            counters.h2d((p, a, m))
            return jnp.asarray(p), jnp.asarray(a), jnp.asarray(m)

    with TraceAnnotation("sched.select", jobs=n_jobs):
        counters.add("select_calls")
        with TraceAnnotation("sched.eg"):
            # two programs: the fill value's conversion and its broadcast
            counters.add("launches", 2)
            u_sum = jnp.zeros((n_pol,), jnp.float32)
        max_w, regrets, hist, raw = [], [], [], []
        ent, top, sim_chunks = [], [], []
        spans = [(lo, min(lo + chunk, n_jobs))
                 for lo in range(0, n_jobs, chunk)]
        staged = _stage(*spans[0])
        for i, (lo, hi) in enumerate(spans):
            pr_c, av_c, pm_c = staged
            jb = fast_sim.slice_jobs(jobs, lo, hi)
            counters.add("launches", sum(
                isinstance(f, jax.Array) and hi - lo < f.shape[0]
                for f in jobs))
            if sharded:
                out = fast_sim.simulate_pool_jobs_sharded(
                    pool_arrays, jb, tput, pr_c, av_c, pm_c,
                    backend=backend, mesh=mesh, collect=collect,
                    fallback=fallback, delta_mig=delta_mig, p_od=p_od,
                )
            else:
                out = fast_sim.simulate_pool_jobs(
                    pool_arrays, jb, tput, pr_c, av_c, pm_c,
                    backend=backend, collect=collect, fallback=fallback,
                    delta_mig=delta_mig, p_od=p_od,
                )
            u = out["utility"]                   # (k, M), device-resident
            with TraceAnnotation("sched.eg"):
                counters.add("launches", 2)      # the column sum, the add
                u_sum = u_sum + jnp.sum(u, axis=0)
                state, traj = counters.launch(
                    _normalize_and_scan, jb, u, state, track_history,
                    collect)
            # everything above is async-dispatched device work; prep the
            # NEXT chunk now so host prep overlaps the in-flight simulation
            if i + 1 < len(spans):
                staged = _stage(*spans[i + 1])
            max_w.append(traj["max_weight"])
            regrets.append(traj["regret"])
            if track_history:
                hist.append(traj["weights"])
            if return_utilities:
                raw.append(u)
            if collect:
                ent.append(traj["entropy"])
                top.append(traj["top_policy"])
                sim_chunks.append(out)

        # the host waits here for the device to finish the call's work
        with TraceAnnotation("sched.fetch"):
            cat = (lambda parts: counters.to_host(parts[0])
                   if len(parts) == 1
                   else np.concatenate([counters.to_host(p) for p in parts]))
            sim_out = None
            if collect:
                sim_out = {k: cat([c[k] for c in sim_chunks])
                           for k in sim_chunks[0]}
            return SelectionResult(
                state=state,
                mean_utility=counters.to_host(u_sum) / n_jobs,
                max_weight=cat(max_w),
                regret=cat(regrets),
                n_jobs=n_jobs,
                weight_history=cat(hist) if track_history else None,
                utilities=cat(raw) if return_utilities else None,
                entropy=cat(ent) if collect else None,
                top_policy=cat(top) if collect else None,
                sim_out=sim_out,
            )
