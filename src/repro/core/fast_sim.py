"""Vectorized policy simulator: one lax.scan over slots, batched over the
whole policy pool (the paper's 112 plus any RAND_DEADLINE / Robust-AHAP
expansions) and over jobs — this is what makes the paper's Fig. 9/10
experiments (1000s of jobs x 100+ policies) take seconds instead of hours.

Semantics mirror repro.core.simulator.simulate exactly (pinned by
tests/test_selector_fastsim.py): same feasibility pipeline, same mu/billing/
termination rules, same rounding (jnp.round == python round, half-to-even).

Policies are encoded as arrays (see policy_pool.specs_to_arrays). The pool
entry points partition the lanes by ``kind``: AHAP lanes run the DP-bearing
scan, where each scan slot issues ONE lane-batched ``solve_window_batch``
call — a single (P_ahap, w1, tn+1) DP (one fused kernel launch on the
Pallas backends) instead of vmap's per-lane grid batching. All other kinds
(AHANP/OD/MSU/UP/RAND_DEADLINE) run a cheap scan that never touches the
window DP, and the results are scattered back to the original pool order —
the public API and semantics are unchanged.

Each lane family has one scan, and the market's rank decides its region
axis: flat (d_max,) market rows trace the single-market program, (R,
d_max) rows add per-slot region selection (SkyNomad arXiv:2601.06520) —
the flat market is the region-less case, not a second scan.

Multi-device: ``simulate_pool_jobs_sharded`` lays the (jobs x lanes) grid
over a mesh (repro.launch.mesh.make_pool_mesh) with ``shard_map``. On the
default 1-D mesh jobs ride the single axis; a 2-D ``("jobs", "lanes")``
mesh (``make_pool_mesh(shape=(a, b))``) additionally shards each kind
partition's policy-lane axis — because the kind partition splits DP-heavy
AHAP lanes from cheap lanes *before* sharding, every lane shard carries a
uniform workload (load balance is by construction). Both grid axes pad to
divisibility, and a single device falls back bitwise-identically to
``simulate_pool_jobs``; the shard_map'd partition runners are built once
per static config (``_sharded_pool_call``) so steady-state calls never
retrace.

The host drivers (:func:`_run_partitioned` and its sharded twin) mark their
steps with ``sched.pool.*`` profiler spans and count their transfers and
launches in ``repro.obs.counters``; named scopes put ``ahap_precompute`` and
the window DP's ``window_dp`` on the device ops' names.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import JobConfig, ThroughputConfig
from repro.core.job import value_fn
from repro.core.policies import RSEL_BIG, RSEL_PRED_WINDOW
from repro.core.policy_pool import (KIND_AHANP, KIND_AHAP, KIND_MSU, KIND_OD,
                                    KIND_RAND, KIND_UP)
from repro.core.window_opt import solve_window, solve_window_batch
from repro.obs import counters

W1MAX = 6   # max omega + 1
VMAX = 5    # max commitment level
NTABLE = 16  # static unit-table width (paper availability cap)


class JobArrays(NamedTuple):
    workload: jnp.ndarray
    deadline: jnp.ndarray       # int32 (dynamic; scan runs d_max slots)
    n_min: jnp.ndarray
    n_max: jnp.ndarray
    value: jnp.ndarray
    gamma: jnp.ndarray
    p_o: jnp.ndarray

    @staticmethod
    def of(job: JobConfig) -> "JobArrays":
        return JobArrays(
            jnp.float32(job.workload), jnp.int32(job.deadline),
            jnp.int32(job.n_min), jnp.int32(job.n_max),
            jnp.float32(job.value), jnp.float32(job.gamma),
            jnp.float32(job.on_demand_price),
        )


def _job_cfg(j: JobArrays) -> JobConfig:
    """JobConfig carrying tracers (fine: frozen dataclass of leaves)."""
    return JobConfig(
        workload=j.workload, deadline=j.deadline, n_min=j.n_min,
        n_max=j.n_max, value=j.value, gamma=j.gamma, on_demand_price=j.p_o,
    )


def _feasible(n_o, n_s, price, avail, j: JobArrays):
    """Mirror of BasePolicy._feasible."""
    n_s = jnp.minimum(jnp.minimum(n_s, avail), j.n_max)
    n_o = jnp.maximum(n_o, 0)
    total = n_o + n_s
    need = jnp.maximum(j.n_min - total, 0)
    spot_room = (price <= j.p_o) & (avail - n_s >= need)
    n_s = jnp.where((total > 0) & (total < j.n_min) & spot_room, n_s + need, n_s)
    n_o = jnp.where((total > 0) & (total < j.n_min) & ~spot_room, n_o + need, n_o)
    over = jnp.maximum(n_o + n_s - j.n_max, 0)
    drop_od = jnp.where(price <= j.p_o, jnp.minimum(over, n_o), 0)
    n_o = n_o - drop_od
    n_s = n_s - (over - drop_od)
    zero = total <= 0
    return jnp.where(zero, 0, n_o), jnp.where(zero, 0, n_s)


def _sim_clip(n_o, n_s, avail, j: JobArrays):
    """Mirror of simulate()'s hard feasibility clip."""
    n_s = jnp.clip(n_s, 0, jnp.minimum(avail, j.n_max))
    n_o = jnp.clip(n_o, 0, j.n_max - n_s)
    n = n_o + n_s
    n_o = jnp.where((n > 0) & (n < j.n_min), n_o + (j.n_min - n), n_o)
    return n_o, n_s


# ---------------------------------------------------------------------------
# Decision rules — shared by the lane scans and the fleet
# ---------------------------------------------------------------------------

def _ahap_precompute(j: JobArrays, omega, sigma, rho, ts, pred):
    """Scan-invariant AHAP scaffolding, vectorized over a leading slot axis
    (or scalar ts with one slot's pred).

    Robust-AHAP discounts *predicted* availability (entries j >= 1 only)."""
    disc_av = jnp.floor(rho * pred[..., 1]).at[..., 0].set(pred[..., 0, 1])
    pr = jnp.stack([pred[..., 0], disc_av], axis=-1)
    in_w = jnp.arange(W1MAX) <= omega
    z_exp_end = j.workload / j.deadline * jnp.minimum(
        (ts + 1 + omega).astype(jnp.float32), j.deadline.astype(jnp.float32)
    )
    thr_s = jnp.where(
        in_w
        & (pr[..., 0] <= sigma * j.p_o)
        & (pr[..., 1] >= j.n_min),
        jnp.minimum(pr[..., 1].astype(jnp.int32), j.n_max),
        0,
    )
    eff_slots = jnp.minimum(j.deadline - ts, omega + 1)
    return pr, thr_s, z_exp_end, eff_slots


def _ahap_rule(jcfg, j: JobArrays, tput, v, backend, z, t, price, av, plans,
               pr_t, thr_s_t, z_exp_end_t, eff_slots_t):
    """AHAP (Alg. 1): CHC window solve when behind, threshold plan when
    ahead, v-step plan averaging. Returns (n_o, n_s, new_plans)."""
    ahead = z >= z_exp_end_t
    chc_o, chc_s, _ = solve_window(
        jcfg, tput, z, eff_slots_t, pr_t[:, 0], pr_t[:, 1].astype(jnp.int32),
        j.p_o, table_n=NTABLE, backend=backend,
    )
    plan = jnp.where(
        ahead,
        jnp.stack([jnp.zeros(W1MAX, jnp.int32), thr_s_t], axis=-1),
        jnp.stack([chc_o, chc_s], axis=-1),
    ).astype(jnp.float32)  # (W1MAX, 2)
    plans = jnp.concatenate([plan[None], plans[:-1]], axis=0)  # (VMAX, W1MAX, 2)
    kk = jnp.arange(VMAX)
    # a plan only exists if it was actually made (k <= t): matches the
    # python policy's growing history, not zero-padded averaging
    valid = ((kk < v) & (kk <= t))[:, None].astype(jnp.float32)
    diag = plans[kk, jnp.minimum(kk, W1MAX - 1)]  # (VMAX, 2)
    cnt = jnp.maximum(valid.sum(), 1.0)
    avg = (diag * valid).sum(axis=0) / cnt
    # round-half-up, matching the python reference exactly
    ah_o = jnp.floor(avg[0] + 0.5).astype(jnp.int32)
    ah_s = jnp.minimum(jnp.floor(avg[1] + 0.5).astype(jnp.int32), av)
    ah_zero = (ah_o + ah_s) == 0
    ah_o_f, ah_s_f = _feasible(ah_o, ah_s, price, av, j)
    ah_o = jnp.where(ah_zero, 0, ah_o_f)
    ah_s = jnp.where(ah_zero, 0, ah_s_f)
    return ah_o, ah_s, plans


def _ahanp_rule(j: JobArrays, sigma, z, t, price, av, n_prev, prev_avail):
    """AHANP (Alg. 3): reactive indicators z_hat / p_hat / n_hat."""
    z_exp_prev = j.workload / j.deadline * t.astype(jnp.float32)
    z_hat = jnp.where(z_exp_prev > 0, z / z_exp_prev, 1.0)
    p_hat = price / (sigma * j.p_o)
    # n_hat = av / prev_avail (0 without supply, inf after none) is only
    # compared with 1/2 and 1, so it is compared in integers: exactly the
    # IEEE float32 quotient's verdict, and immune to a device whose
    # division is not correctly rounded (x / x above 1 on a TPU for some
    # supplies in the thousands)
    n_hat_le = lambda num: (av == 0) | ((prev_avail != 0)
                                        & (num * av <= prev_avail))
    ahead1 = z_hat >= 1.0
    n_an = jnp.where(
        ahead1,
        jnp.where(
            av == 0,
            0,
            jnp.where(
                n_hat_le(2),
                jnp.maximum(n_prev // 2, j.n_min),
                jnp.where(
                    n_hat_le(1),
                    n_prev,
                    jnp.where(p_hat > 1.0, n_prev, jnp.maximum(n_prev, av)),
                ),
            ),
        ),
        jnp.maximum(2 * n_prev, j.n_min),
    )
    an_zero = n_an <= 0
    n_an_c = jnp.clip(n_an, j.n_min, j.n_max)
    an_s = jnp.minimum(av, n_an_c)
    an_o_f, an_s_f = _feasible(n_an_c - an_s, an_s, price, av, j)
    an_o = jnp.where(an_zero, 0, an_o_f)
    an_s = jnp.where(an_zero, 0, an_s_f)
    return an_o, an_s


def _od_rule(j: JobArrays, tput, z, t, price, av):
    """OD-Only: constant on-demand sized to finish exactly at the deadline."""
    remaining = jnp.maximum(j.workload - z, 0.0)
    slots_left = (j.deadline - t).astype(jnp.float32)
    od_need = jnp.ceil(
        remaining / jnp.maximum(slots_left, 1.0) / tput.alpha
    ).astype(jnp.int32)
    od_zero = (remaining <= 0) | (slots_left <= 0)
    od_o_f, od_s_f = _feasible(jnp.clip(od_need, j.n_min, j.n_max), 0, price, av, j)
    od_o = jnp.where(od_zero, 0, od_o_f)
    od_s = jnp.where(od_zero, 0, od_s_f)
    return od_o, od_s


def _msu_rule(j: JobArrays, tput, z, t, price, av):
    """MSU: all spot; on-demand only once N^max can no longer finish."""
    remaining = jnp.maximum(j.workload - z, 0.0)
    slots_left = (j.deadline - t).astype(jnp.float32)
    od_need = jnp.ceil(
        remaining / jnp.maximum(slots_left, 1.0) / tput.alpha
    ).astype(jnp.int32)
    ms_s = jnp.minimum(av, j.n_max)
    h_max = tput.alpha * j.n_max.astype(jnp.float32) + tput.beta
    panic = remaining > h_max * jnp.maximum(slots_left - 1.0, 0.0)
    ms_o = jnp.where(
        panic,
        jnp.maximum(jnp.minimum(od_need, j.n_max) - ms_s, 0),
        0,
    )
    ms_zero = (remaining <= 0) | ((ms_s + ms_o) == 0)
    ms_o_f, ms_s_f = _feasible(ms_o, ms_s, price, av, j)
    ms_o = jnp.where(ms_zero, 0, ms_o_f)
    ms_s = jnp.where(ms_zero, 0, ms_s_f)
    return ms_o, ms_s


def _up_rule(j: JobArrays, tput, z, t, price, av):
    """UP (Wu et al. [16]): track the L/d line, spot-first."""
    remaining = jnp.maximum(j.workload - z, 0.0)
    rate = j.workload / j.deadline.astype(jnp.float32)
    deficit = jnp.maximum(rate * t.astype(jnp.float32) - z, 0.0)
    up_need = jnp.clip(
        jnp.ceil((rate + deficit) / tput.alpha).astype(jnp.int32), j.n_min, j.n_max
    )
    up_s = jnp.minimum(av, up_need)
    up_o = jnp.where(deficit > 0, up_need - up_s, 0)
    up_zero = (remaining <= 0) | ((up_s + up_o) == 0)
    up_o_f, up_s_f = _feasible(up_o, up_s, price, av, j)
    up_o = jnp.where(up_zero, 0, up_o_f)
    up_s = jnp.where(up_zero, 0, up_s_f)
    return up_o, up_s


def _rand_rule(j: JobArrays, tput, cfrac, z, t, price, av):
    """RAND_DEADLINE (arXiv:2601.14612): randomized commitment threshold.
    All-spot before the committed slot tau = floor(cfrac * d); from tau on,
    on-demand sized to finish exactly at the deadline. ``cfrac`` is the
    inverse optimal-commitment CDF at the lane's quantile, precomputed in
    float64 by specs_to_arrays, so the f32 floor here matches the python
    reference bit-for-bit."""
    tau = jnp.floor(cfrac * j.deadline.astype(jnp.float32))
    committed = t.astype(jnp.float32) >= tau
    remaining = jnp.maximum(j.workload - z, 0.0)
    slots_left = (j.deadline - t).astype(jnp.float32)
    od_need = jnp.ceil(
        remaining / jnp.maximum(slots_left, 1.0) / tput.alpha
    ).astype(jnp.int32)
    rd_o = jnp.where(committed, jnp.clip(od_need, j.n_min, j.n_max), 0)
    rd_s = jnp.where(committed, 0, jnp.minimum(av, j.n_max))
    rd_zero = (remaining <= 0) | (slots_left <= 0) | ((rd_o + rd_s) == 0)
    rd_o_f, rd_s_f = _feasible(rd_o, rd_s, price, av, j)
    rd_o = jnp.where(rd_zero, 0, rd_o_f)
    rd_s = jnp.where(rd_zero, 0, rd_s_f)
    return rd_o, rd_s


_CHEAP_KINDS = (KIND_AHANP, KIND_OD, KIND_MSU, KIND_UP, KIND_RAND)


def _cheap_rules(kind, j: JobArrays, tput, sigma, cfrac, z, t, price, av,
                 n_prev, prev_avail):
    """Every DP-free kind's rule, in ``_CHEAP_KINDS`` order — the one place
    a kind id is mapped to its rule. Returns ``(conds, n_o candidates,
    n_s candidates)`` for ``jnp.select``; ``conds()`` builds the ``kind ==
    k`` masks, once for each select that takes them."""
    cands = (
        _ahanp_rule(j, sigma, z, t, price, av, n_prev, prev_avail),
        _od_rule(j, tput, z, t, price, av),
        _msu_rule(j, tput, z, t, price, av),
        _up_rule(j, tput, z, t, price, av),
        _rand_rule(j, tput, cfrac, z, t, price, av),
    )
    conds = lambda: [kind == k for k in _CHEAP_KINDS]
    return conds, [o for o, _ in cands], [s for _, s in cands]


def _execute(j: JobArrays, tput, z, n_prev, cost, done, T, t, n_o, n_s,
             price, av):
    """Mirror of simulate()'s slot execution: hard clip, mu, billing,
    fractional completion. Returns the updated exec state + (n_o, n_s, active)."""
    active = (t < j.deadline) & ~done
    n_o, n_s = _sim_clip(n_o, n_s, av, j)
    n_o = jnp.where(active, n_o, 0)
    n_s = jnp.where(active, n_s, 0)
    n = n_o + n_s

    mu = jnp.where(n > n_prev, tput.mu1, jnp.where(n < n_prev, tput.mu2, 1.0))
    mu = jnp.where((n == 0) & (n_prev == 0), 1.0, mu)
    work = mu * jnp.where(n > 0, tput.alpha * n.astype(jnp.float32) + tput.beta, 0.0)
    will_done = active & (work > 0) & (z + work >= j.workload)
    frac = jnp.where(work > 0, (j.workload - z) / jnp.maximum(work, 1e-9), 0.0)
    T = jnp.where(will_done, t.astype(jnp.float32) + frac, T)
    cost = cost + jnp.where(
        active, n_s.astype(jnp.float32) * price + n_o.astype(jnp.float32) * j.p_o, 0.0
    )
    z = jnp.minimum(z + jnp.where(active, work, 0.0), j.workload)
    n_prev = jnp.where(active, n, n_prev)
    done = done | will_done
    return z, n_prev, cost, done, T, n_o, n_s, active


# flight-recorder slot series (repro.obs): emitted as extra stacked scan
# outputs when a pool entry point runs with collect=True, riding the result
# dict as flat "tel_*" keys (same (.., T) layout as n_od/n_spot) so the
# scatter-merge / shard_map / padding plumbing carries them unchanged.
# Order matches _slot_telemetry's return tuple.
_TEL_SLOTS = ("tel_spot_cost", "tel_od_cost", "tel_progress", "tel_active",
              "tel_up", "tel_down", "tel_preempt")

# prediction-health series, emitted ONLY when a collect run also enables
# the fallback monitor (fallback is not None): plain collect runs keep the
# exact _TEL_SLOTS key set the subprocess parity tests count on.
# Order matches the (fallback-active, ewma-error) ys appended by the scans.
_TEL_FALLBACK = ("tel_fallback", "tel_pred_err")

# region-path series, emitted only by the multi-region scans under collect:
# the region occupied each slot and the switch-decision events. The slot
# sums of tel_migration must equal the ``migrations`` result leaves
# (reconciled in repro.obs.ledger.migration_reconciliation).
_TEL_REGION = ("tel_region", "tel_migration")

# floor for the relative-error denominators of the fallback monitor
# (traces clip prices >= 0.02; availability errors normalize by >= 1 unit)
_FB_PRICE_EPS = 0.01


def _fallback_error(fallback, err, price, av, prev1_t):
    """One EWMA update of the prediction-health monitor: blend the relative
    errors of last slot's 1-step-ahead forecast ``prev1_t`` (price, avail)
    against this slot's observed market. All ``fallback`` fields are static
    constants baked into the trace; only traced when fallback is enabled."""
    avf = av.astype(jnp.float32)
    e_p = jnp.abs(price - prev1_t[0]) / jnp.maximum(price, _FB_PRICE_EPS)
    e_a = jnp.abs(avf - prev1_t[1]) / jnp.maximum(avf, 1.0)
    w_p = jnp.float32(fallback.price_weight)
    e = w_p * e_p + (jnp.float32(1.0) - w_p) * e_a
    lam = jnp.float32(fallback.lam)
    return (jnp.float32(1.0) - lam) * err + lam * e


def _fallback_prev1(pred):
    """(T, 2) realized 1-step-ahead forecast series: at slot t, the value
    the predictor issued at t-1 for t. Slot 0 uses its own observed-present
    row, so the monitor starts cold (zero error)."""
    return jnp.concatenate([pred[:1, 0, :], pred[:-1, 1, :]], axis=0)


def _slot_telemetry(j: JobArrays, n_prev_before, z, n_o, n_s, active,
                    price, av):
    """One flight-recorder sample, taken AFTER :func:`_execute` ran the
    slot: the spot/on-demand cost split billed this slot, cumulative
    progress, and the reconfiguration events — ``preempt`` flags a shrink
    forced by supply (available spot — the fleet's waterfall grant — fell
    below last slot's allocation). Pure elementwise ops; the collect=False
    path never traces this function, which is what keeps the default
    program bitwise-identical to the pre-telemetry build."""
    n = n_o + n_s
    act_f = active.astype(jnp.float32)
    up = active & (n > n_prev_before)
    down = active & (n < n_prev_before)
    preempt = down & (av < n_prev_before)
    return (
        act_f * n_s.astype(jnp.float32) * price,
        act_f * n_o.astype(jnp.float32) * j.p_o,
        z,
        active,
        up,
        down,
        preempt,
    )


def _finalize(jcfg, j: JobArrays, tput, z, cost, done, T, no_hist, ns_hist):
    """Termination configuration (N^max on-demand past the deadline)."""
    h_max = tput.alpha * j.n_max.astype(jnp.float32) + tput.beta
    dt = jnp.maximum(j.workload - z, 0.0) / h_max
    T_final = jnp.where(done, T, j.deadline.astype(jnp.float32) + dt)
    cost_final = cost + jnp.where(done, 0.0, j.p_o * j.n_max.astype(jnp.float32) * dt)
    value = value_fn(jcfg, T_final)
    return {
        "utility": value - cost_final,
        "value": value,
        "cost": cost_final,
        "completion_time": T_final,
        "z_ddl": z,
        "completed": done,
        "n_od": no_hist,
        "n_spot": ns_hist,
    }


# ---------------------------------------------------------------------------
# Region selection (BEYOND-PAPER, SkyNomad arXiv:2601.06520)
# ---------------------------------------------------------------------------
#
# Given (R, d_max) market rows, the lane scans layer per-slot region
# selection over the unmodified decision rules: every lane carries a
# current-region state, scores all regions each slot (vectorized, from data
# precomputed outside the scan), switches with a hysteresis margin, pays
# ``delta_mig`` zero-allocation slots per switch (checkpoint transfer), and
# feeds the selected region's (price, avail, forecast) into the rules. With
# R == 1 the selector can never leave region 0 and every migration branch is
# a no-op ``where`` passthrough, so results are BITWISE-identical to the
# flat market's (pinned in tests/test_region_sim.py).

# the pred_horizon score averages a fixed-width forecast window; the python
# reference (policies.RegionSelector.scores) pads/trims to the same width
assert RSEL_PRED_WINDOW == W1MAX


def _region_scores(j: JobArrays, prices, avail, pred):
    """(dmax, 4, R) lower-better scores from (R, dmax) market data and
    (R, dmax, W1MAX, 2) forecasts — the jnp twin of
    policies.RegionSelector.scores, all four RSEL_* strategies at once
    (lanes gather their row by ``rsel``). Scan-invariant: computed once per
    (job, trace)."""
    nmin_f = j.n_min.astype(jnp.float32)
    dead = (avail < j.n_min).astype(jnp.float32)
    price_sc = prices + RSEL_BIG * dead                   # (R, dmax)
    avail_sc = -avail.astype(jnp.float32)
    pdead = (pred[..., 1] < nmin_f).astype(jnp.float32)
    pred_sc = jnp.mean(pred[..., 0] + RSEL_BIG * pdead, axis=-1)
    sc = jnp.stack(
        [jnp.zeros_like(price_sc), price_sc, avail_sc, pred_sc]
    )                                                     # (4, R, dmax)
    return jnp.transpose(sc, (2, 0, 1))


def _region_step(cur, mig_left, sc_row, rmargin, delta_mig: int, inactive):
    """One slot of region selection: argmin with hysteresis + migration
    bookkeeping. Batched over lanes (cur/mig_left (P,), sc_row (P, R)) or
    scalar (cur/mig_left scalars, sc_row (R,)). Returns
    (cur, mig_left, migrating, switched); ``migrating`` slots execute with
    zero instances (the checkpoint is in transit). ``inactive`` lanes
    (completed, or past their deadline in a heterogeneous-deadline batch)
    never switch — the reference loop has stopped by then, so late score
    flips must not move (or count against) such a job."""
    best = jnp.argmin(sc_row, axis=-1).astype(jnp.int32)
    cur_sc = jnp.take_along_axis(sc_row, cur[..., None], -1)[..., 0]
    best_sc = jnp.take_along_axis(sc_row, best[..., None], -1)[..., 0]
    switch = ((best != cur) & (best_sc + rmargin < cur_sc)
              & (mig_left == 0) & ~inactive)
    cur = jnp.where(switch, best, cur)
    mig_left = jnp.where(
        switch, jnp.int32(delta_mig), jnp.maximum(mig_left - 1, 0)
    )
    return cur, mig_left, mig_left > 0, switch


def _occupied(j: JobArrays, jcfg, p_od, cur):
    """The job as billed in region ``cur``: its on-demand price scaled by
    the region's ``p_od`` multiplier; unchanged (no ops traced) without
    one."""
    if p_od is None:
        return j, jcfg
    j = j._replace(p_o=j.p_o * p_od[cur])
    return j, _job_cfg(j)


# ---------------------------------------------------------------------------
# Kind-partitioned lane scans (the hot path)
# ---------------------------------------------------------------------------

def _ahap_rule_batch(jcfg, j: JobArrays, tput, v, backend, z, t, price, av,
                     plans, pr_t, thr_t, zee_t, eff_t):
    """Lane-batched :func:`_ahap_rule`: z/v/eff_t are (P,), pr_t is
    (P, W1MAX, 2), plans is (P, VMAX, W1MAX, 2). The CHC solve is ONE
    ``solve_window_batch`` call across all lanes — a single fused kernel
    launch per slot on the Pallas backends. Elementwise ops broadcast over
    the lane axis, so results are bitwise-equal to the per-lane rule.

    ``t`` may be a scalar (all lanes share the slot clock, the pool path)
    or a (P,) vector of per-lane local clocks (the fleet path, where lanes
    are jobs with different arrivals); scalar callers are unchanged
    bitwise. In the vector case ``jcfg``/``j``/``av`` may be per-lane too —
    ``solve_window_batch`` and the elementwise rules broadcast them."""
    p = z.shape[0]
    ahead = z >= zee_t
    chc_o, chc_s, _ = solve_window_batch(
        jcfg, tput, z, eff_t, pr_t[..., 0], pr_t[..., 1].astype(jnp.int32),
        j.p_o, table_n=NTABLE, backend=backend,
    )
    plan = jnp.where(
        ahead[:, None, None],
        jnp.stack([jnp.zeros((p, W1MAX), jnp.int32), thr_t], axis=-1),
        jnp.stack([chc_o, chc_s], axis=-1),
    ).astype(jnp.float32)                               # (P, W1MAX, 2)
    plans = jnp.concatenate([plan[:, None], plans[:, :-1]], axis=1)
    kk = jnp.arange(VMAX)
    t_arr = jnp.asarray(t)
    made = kk[None, :] <= (t_arr[:, None] if t_arr.ndim else t_arr)
    valid = (kk[None, :] < v[:, None]) & made
    valid = valid[..., None].astype(jnp.float32)        # (P, VMAX, 1)
    diag = plans[:, kk, jnp.minimum(kk, W1MAX - 1)]     # (P, VMAX, 2)
    cnt = jnp.maximum(valid.sum(axis=(1, 2)), 1.0)      # (P,)
    avg = (diag * valid).sum(axis=1) / cnt[:, None]     # (P, 2)
    ah_o = jnp.floor(avg[:, 0] + 0.5).astype(jnp.int32)
    ah_s = jnp.minimum(jnp.floor(avg[:, 1] + 0.5).astype(jnp.int32), av)
    ah_zero = (ah_o + ah_s) == 0
    ah_o_f, ah_s_f = _feasible(ah_o, ah_s, price, av, j)
    ah_o = jnp.where(ah_zero, 0, ah_o_f)
    ah_s = jnp.where(ah_zero, 0, ah_s_f)
    return ah_o, ah_s, plans


def _simulate_lanes_ahap(omega, v, sigma, rho, j: JobArrays, tput,
                         prices, avail, pred, backend: str,
                         collect: bool = False, fallback=None, rsel=None,
                         rmargin=None, delta_mig=None, p_od=None):
    """All AHAP lanes in ONE scan over slots. Each scan slot issues a single
    batched (P_ahap, w1, tn+1) window DP instead of relying on vmap's
    per-lane grid batching (``_simulate_one_ahap`` under vmap — kept below
    as the equivalence oracle). Scan-invariant scaffolding is precomputed
    per (lane, slot) and fed slot-major through the scan xs. ``collect``
    (static) appends the ``_TEL_SLOTS`` flight-recorder series to the scan
    ys — the False branch traces the identical program.

    ``fallback`` (a static :class:`repro.chaos.FallbackConfig`, or None)
    arms the online prediction-health monitor: the scan carries a
    realized-forecast-error EWMA and, while it exceeds the threshold, the
    lane's decision is taken from the prediction-free AHANP rule instead
    of the window solve (the AHANP "previous availability" is the shifted
    supply, matching the fleet engine's convention). Plans keep updating
    underneath so recovery resumes AHAP with a warm history. ``None``
    traces the bitwise-identical shipped program; with collect also on,
    the ``_TEL_FALLBACK`` series join the ys.

    Market rows with a region axis — prices/avail (R, dmax), pred
    (R, dmax, W1MAX, 2) — make this the regional scan: the scaffolding is
    precomputed per (lane, region, slot), each slot selects a region per
    lane (``rsel``/``rmargin`` strategy, ``delta_mig`` lost slots per
    switch) and gathers that region's row before the CHC rule, and the
    result gains ``region`` and ``migrations`` (under collect, the
    ``_TEL_REGION`` pair too). The fallback EWMA is then per lane (P,):
    lanes occupy different regions, so each scores the 1-step-ahead
    forecast of ITS region (flat rows share one scalar — every lane of a
    job reads the same forecast stack). ``p_od`` (traced (R,) array, or
    None) scales the on-demand price per region (termination billing uses
    the lane's final region)."""
    regional = prices.ndim == 2
    dmax = prices.shape[-1]
    p = omega.shape[0]
    jcfg = _job_cfg(j)
    ts = jnp.arange(dmax)
    # per-region od price: thr_s thresholds see the (R, 1) effective price
    # broadcast against the (R, W1MAX) forecast rows
    j_pre = j if p_od is None else j._replace(p_o=j.p_o * p_od[:, None])
    # slot-major from the start: slots on the OUTER vmap, lanes inner, so the
    # scan-xs layout (dmax leading) is the only one ever materialized. The
    # old lane-major vmap + per-tensor swapaxes built the (P, dmax, ...)
    # tensors AND their transposed copies at every scan boundary — at Fig.
    # 9/10 scale (1000 jobs x 105 AHAP lanes) that doubled the largest
    # buffers in the whole simulation for pure data movement. A regional
    # forecast stack is transposed ONCE (small) to (dmax, R, W1MAX, 2).
    with jax.named_scope("ahap_precompute"):
        pr, thr_s, z_exp_end, eff_slots = jax.vmap(
            lambda t, pm: jax.vmap(
                lambda w, s, r: _ahap_precompute(j_pre, w, s, r, t, pm)
            )(omega, sigma, rho)
        )(ts, jnp.swapaxes(pred, 0, 1) if regional else pred)
    # pr (dmax, P, [R,] W1MAX, 2); thr_s (dmax, P, [R,] W1MAX); rest (dmax, P)
    av_i = avail.astype(jnp.int32)
    market = (prices, av_i)                      # slot-major scan xs
    if regional:
        sc = _region_scores(j, prices, av_i, pred)[:, rsel]  # (dmax, P, R)
        lane = jnp.arange(p)
        market = (jnp.swapaxes(prices, 0, 1), jnp.swapaxes(av_i, 0, 1))
    if fallback is not None:
        thr = jnp.float32(fallback.threshold)
        prev1 = (jnp.swapaxes(jax.vmap(_fallback_prev1)(pred), 0, 1)
                 if regional else _fallback_prev1(pred))   # (dmax, [R,] 2)
        prev_av = jnp.concatenate([market[1][:1], market[1][:-1]])

    def step(carry, xs):
        (z, n_prev, cost, done, T, plans), reg, fb_c = carry
        price, av, pr_t, thr_t, zee_t, eff_t, reg_xs, t, fb_xs = xs
        j_t, jcfg_t = j, jcfg
        reg_ys = ()
        if regional:
            (cur, mig_left), (sc_t,) = reg, reg_xs
            cur, mig_left, migrating, switch = _region_step(
                cur, mig_left, sc_t, rmargin, delta_mig,
                done | (t >= j.deadline),
            )
            price, av = price[cur], av[cur]      # (P,) per-lane region rows
            pr_t, thr_t = pr_t[lane, cur], thr_t[lane, cur]
            j_t, jcfg_t = _occupied(j, jcfg, p_od, cur)
            reg, reg_ys = (cur, mig_left), (cur, switch)
        if fallback is not None:
            p1_t, pav_t = fb_xs
            if regional:
                p1_t, pav_t = p1_t[cur].T, pav_t[cur]  # lane-region rows
            err = _fallback_error(fallback, *fb_c, price, av, p1_t)
            fb, fb_c = err > thr, (err,)
        n_o, n_s, plans = _ahap_rule_batch(
            jcfg_t, j_t, tput, v, backend, z, t, price, av, plans,
            pr_t, thr_t, zee_t, eff_t,
        )
        if fallback is not None:
            an_o, an_s = _ahanp_rule(j_t, sigma, z, t, price, av, n_prev,
                                     pav_t)
            n_o = jnp.where(fb, an_o, n_o)
            n_s = jnp.where(fb, an_s, n_s)
        if regional:
            n_o = jnp.where(migrating, 0, n_o)
            n_s = jnp.where(migrating, 0, n_s)
        n_prev0 = n_prev
        z, n_prev, cost, done, T, n_o, n_s, active = _execute(
            j_t, tput, z, n_prev, cost, done, T, t, n_o, n_s, price, av
        )
        ys = (n_o, n_s) + reg_ys
        if collect:
            ys = ys + _slot_telemetry(j_t, n_prev0, z, n_o, n_s, active,
                                      price, av) + reg_ys
            if fallback is not None:
                ys = ys + (jnp.broadcast_to(fb, n_o.shape),
                           jnp.broadcast_to(err, n_o.shape))
        return ((z, n_prev, cost, done, T, plans), reg, fb_c), ys

    init = (
        jnp.zeros((p,), jnp.float32), jnp.zeros((p,), jnp.int32),
        jnp.zeros((p,), jnp.float32), jnp.zeros((p,), jnp.bool_),
        jnp.zeros((p,), jnp.float32),
        jnp.zeros((p, VMAX, W1MAX, 2), jnp.float32),
    )
    reg0 = reg_xs = fb0 = fb_xs = ()
    if regional:
        # free initial placement: each lane starts in its best region
        reg0 = (jnp.argmin(sc[0], axis=-1).astype(jnp.int32),
                jnp.zeros((p,), jnp.int32))
        reg_xs = (sc,)
    if fallback is not None:
        fb0 = (jnp.zeros((p,), jnp.float32) if regional
               else jnp.float32(0.0),)
        fb_xs = (prev1, prev_av)
    ((z, _, cost, done, T, _), reg_end, _), ys = jax.lax.scan(
        step, (init, reg0, fb0),
        (*market, pr, thr_s, z_exp_end, eff_slots, reg_xs, ts, fb_xs),
    )
    n_hist = 4 if regional else 2
    j_fin, jcfg_fin = (_occupied(j, jcfg, p_od, reg_end[0]) if regional
                       else (j, jcfg))
    out = _finalize(jcfg_fin, j_fin, tput, z, cost, done, T,
                    jnp.swapaxes(ys[0], 0, 1), jnp.swapaxes(ys[1], 0, 1))
    if regional:
        out["region"] = jnp.swapaxes(ys[2], 0, 1)
        out["migrations"] = ys[3].astype(jnp.int32).sum(axis=0)
    if collect:
        keys = (_TEL_SLOTS + (_TEL_REGION if regional else ())
                + (_TEL_FALLBACK if fallback is not None else ()))
        for key, hist in zip(keys, ys[n_hist:]):
            out[key] = jnp.swapaxes(hist, 0, 1)
    return out


def _simulate_one_ahap(omega, v, sigma, rho, j: JobArrays, tput,
                       prices, avail, pred, backend: str):
    """AHAP-only lane, one lane per call (the pre-batching formulation —
    ``jax.vmap`` of this is the equivalence oracle for
    ``_simulate_lanes_ahap``). All scan-invariant scaffolding
    (rho-discounted forecasts, threshold plans, schedule line, effective
    window lengths) is hoisted out of the step."""
    dmax = prices.shape[0]
    jcfg = _job_cfg(j)
    ts = jnp.arange(dmax)
    pr, thr_s, z_exp_end, eff_slots = _ahap_precompute(
        j, omega, sigma, rho, ts, pred
    )

    def step(carry, xs):
        z, n_prev, cost, done, T, plans = carry
        price, av, pr_t, thr_s_t, zee_t, eff_t, t = xs
        n_o, n_s, plans = _ahap_rule(
            jcfg, j, tput, v, backend, z, t, price, av, plans,
            pr_t, thr_s_t, zee_t, eff_t,
        )
        z, n_prev, cost, done, T, n_o, n_s, _ = _execute(
            j, tput, z, n_prev, cost, done, T, t, n_o, n_s, price, av
        )
        return (z, n_prev, cost, done, T, plans), (n_o, n_s)

    init = (
        jnp.float32(0.0), jnp.int32(0), jnp.float32(0.0),
        jnp.bool_(False), jnp.float32(0.0),
        jnp.zeros((VMAX, W1MAX, 2), jnp.float32),
    )
    (z, _, cost, done, T, _), (no_hist, ns_hist) = jax.lax.scan(
        step, init,
        (prices, avail.astype(jnp.int32), pr, thr_s, z_exp_end, eff_slots, ts),
    )
    return _finalize(jcfg, j, tput, z, cost, done, T, no_hist, ns_hist)


def _simulate_one_cheap(kind, sigma, cfrac, j: JobArrays, tput, prices, avail,
                        collect: bool = False, fallback=None, rsel=None,
                        rmargin=None, scores=None, delta_mig=None, p_od=None):
    """Non-AHAP lane (AHANP/OD/MSU/UP/RAND_DEADLINE): no forecasts, no
    window DP — the whole step is a handful of VPU ops, ``kind`` picking
    among :func:`_cheap_rules`. ``collect`` (static) appends the
    ``_TEL_SLOTS`` series to the scan ys. Cheap lanes consume no
    predictions, so ``fallback`` never changes their decisions; it only
    (with collect) appends all-zero ``_TEL_FALLBACK`` placeholder series so
    the merged pool result keeps one uniform key set.

    Region-axis rows (prices/avail (R, dmax)) select a region each slot as
    :func:`_simulate_lanes_ahap` does, from ``scores`` — the
    (dmax, N_RSEL, R) tensor of :func:`_region_scores`, shared across the
    cheap lanes of one job — and add the same ``region``/``migrations``
    leaves and ``_TEL_REGION`` series; ``p_od`` as there."""
    regional = prices.ndim == 2
    dmax = prices.shape[-1]
    jcfg = _job_cfg(j)
    if regional:
        av_i = avail.astype(jnp.int32)
        sc = scores[:, rsel]                              # (dmax, R)
        cur0 = jnp.argmin(sc[0]).astype(jnp.int32)

    def step(carry, xs):
        (z, n_prev, cost, done, T, prev_avail), reg = carry
        price, av, reg_xs, t = xs
        j_t = j
        reg_ys = ()
        if regional:
            (cur, mig_left), (sc_t,) = reg, reg_xs
            cur, mig_left, migrating, switch = _region_step(
                cur, mig_left, sc_t, rmargin, delta_mig,
                done | (t >= j.deadline),
            )
            price, av = price[cur], av[cur]
            j_t, _ = _occupied(j, jcfg, p_od, cur)
            reg, reg_ys = (cur, mig_left), (cur, switch)
        conds, cand_o, cand_s = _cheap_rules(
            kind, j_t, tput, sigma, cfrac, z, t, price, av, n_prev,
            prev_avail,
        )
        n_o = jnp.select(conds(), cand_o)
        n_s = jnp.select(conds(), cand_s)
        if regional:
            n_o = jnp.where(migrating, 0, n_o)
            n_s = jnp.where(migrating, 0, n_s)
        n_prev0 = n_prev
        z, n_prev, cost, done, T, n_o, n_s, active = _execute(
            j_t, tput, z, n_prev, cost, done, T, t, n_o, n_s, price, av
        )
        prev_avail = jnp.where(active, av, prev_avail)
        ys = (n_o, n_s) + reg_ys
        if collect:
            ys = ys + _slot_telemetry(j_t, n_prev0, z, n_o, n_s, active,
                                      price, av) + reg_ys
            if fallback is not None:
                ys = ys + (jnp.bool_(False), jnp.float32(0.0))
        return ((z, n_prev, cost, done, T, prev_avail), reg), ys

    init = (
        jnp.float32(0.0), jnp.int32(0), jnp.float32(0.0),
        jnp.bool_(False), jnp.float32(0.0),
    )
    if regional:
        init = (init + (av_i[cur0, 0],), (cur0, jnp.int32(0)))
        xs = (jnp.swapaxes(prices, 0, 1), jnp.swapaxes(av_i, 0, 1), (sc,),
              jnp.arange(dmax))
    else:
        init = (init + (avail[0].astype(jnp.int32),), ())
        xs = (prices, avail.astype(jnp.int32), (), jnp.arange(dmax))
    ((z, _, cost, done, T, _), reg_end), ys = jax.lax.scan(step, init, xs)
    n_hist = 4 if regional else 2
    j_fin, jcfg_fin = (_occupied(j, jcfg, p_od, reg_end[0]) if regional
                       else (j, jcfg))
    out = _finalize(jcfg_fin, j_fin, tput, z, cost, done, T, ys[0], ys[1])
    if regional:
        out["region"] = ys[2]
        out["migrations"] = ys[3].astype(jnp.int32).sum()
    if collect:
        keys = (_TEL_SLOTS + (_TEL_REGION if regional else ())
                + (_TEL_FALLBACK if fallback is not None else ()))
        for key, hist in zip(keys, ys[n_hist:]):
            out[key] = hist
    return out


# ---------------------------------------------------------------------------
# Pool entry points: partition by kind, scatter back to pool order
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("tput", "backend", "collect",
                                             "fallback", "delta_mig"))
def _pool_jobs_ahap(omega, v, sigma, rho, jobs: JobArrays, tput,
                    prices, avail, pred, backend: str, collect: bool = False,
                    fallback=None, rsel=None, rmargin=None, delta_mig=None,
                    p_od=None):
    def per_job(job_row, pr_, av_, pm_):
        return _simulate_lanes_ahap(
            omega, v, sigma, rho, job_row, tput, pr_, av_, pm_, backend,
            collect=collect, fallback=fallback, rsel=rsel, rmargin=rmargin,
            delta_mig=delta_mig, p_od=p_od,
        )

    return jax.vmap(per_job)(jobs, prices, avail, pred)


@functools.partial(jax.jit, static_argnames=("tput", "collect", "fallback",
                                             "delta_mig"))
def _pool_jobs_cheap(kind, sigma, cfrac, jobs: JobArrays, tput, prices, avail,
                     collect: bool = False, fallback=None, rsel=None,
                     rmargin=None, pred=None, delta_mig=None, p_od=None):
    # cheap lanes take no forecasts: ``pred`` (region-axis runs only) feeds
    # the region scores, shared by every cheap lane of a job
    def per_job(job_row, pr_, av_, pm_):
        scores = (None if pm_ is None else
                  _region_scores(job_row, pr_, av_.astype(jnp.int32), pm_))
        fn = lambda k, s, c, rs, rm: _simulate_one_cheap(
            k, s, c, job_row, tput, pr_, av_, collect=collect,
            fallback=fallback, rsel=rs, rmargin=rm, scores=scores,
            delta_mig=delta_mig, p_od=p_od,
        )
        return jax.vmap(fn)(kind, sigma, cfrac, rsel, rmargin)

    return jax.vmap(per_job)(jobs, prices, avail, pred)


def _partition(pool_arrays: dict):
    """(ahap_idx, other_idx, rho, cfrac) as concrete numpy — the pool
    encoding is data, not a tracer, so the split happens once at trace/call
    time."""
    kind = np.asarray(pool_arrays["kind"])
    n = len(kind)
    rho = pool_arrays.get("rho")
    rho = np.ones(n, np.float32) if rho is None else np.asarray(rho, np.float32)
    cfrac = pool_arrays.get("cfrac")
    cfrac = (np.zeros(n, np.float32) if cfrac is None
             else np.asarray(cfrac, np.float32))
    ahap_idx = np.flatnonzero(kind == KIND_AHAP)
    other_idx = np.flatnonzero(kind != KIND_AHAP)
    return ahap_idx, other_idx, rho, cfrac


@functools.lru_cache(maxsize=None)
def _merge_program(order: bytes, axis: int):
    """The jitted scatter-merge for one pool permutation: every result leaf
    of every kind partition is concatenated along ``axis`` and gathered
    back to pool order in ONE program. ``order`` (int32 bytes) is a
    constant of the program, so a call hands the device nothing but the
    partitions' own (device) results; jit compiles once per leaf shape."""
    perm = np.frombuffer(order, np.int32)

    def scatter_merge(parts):
        return {
            k: jnp.take(jnp.concatenate([p[k] for p in parts], axis=axis),
                        perm, axis=axis)
            for k in parts[0]
        }

    return jax.jit(scatter_merge)


def _scatter_merge(parts, index_arrays, axis: int):
    """Stitch per-partition result dicts back into original pool order: one
    launch of :func:`_merge_program`, cached on the permutation and axis."""
    if len(parts) == 1:
        return parts[0]
    with TraceAnnotation("sched.pool.merge"):
        order = np.argsort(np.concatenate(index_arrays), kind="stable")
        merge = _merge_program(order.astype(np.int32).tobytes(), axis)
        return counters.launch(merge, parts)


def _device(x):
    """``jnp.asarray(x)``, counted as a transfer when ``x`` is on the host."""
    counters.h2d(x)
    return jnp.asarray(x)


def _partition_lane_args(pool_arrays: dict, regional: bool):
    """(ahap_idx, other_idx, ahap_args, cheap_args): the per-partition lane
    parameter tuples (numpy) shared by the local and sharded drivers —
    slicing lives in ONE place so a new pool-array slot cannot be wired into
    one driver and silently zero-defaulted in the other. For a ``regional``
    run each tuple additionally carries the partition's (rsel, rmargin)
    region-strategy slices (defaulting to stay-put lanes when the pool
    encoding predates the region slots)."""
    ahap_idx, other_idx, rho, cfrac = _partition(pool_arrays)
    arr = lambda k: np.asarray(pool_arrays[k])
    n = len(arr("kind"))
    extras = lambda idx: ()
    if regional:
        rsel = pool_arrays.get("rsel")
        rsel = (np.zeros(n, np.int32) if rsel is None
                else np.asarray(rsel, np.int32))
        rmargin = pool_arrays.get("rmargin")
        rmargin = (np.zeros(n, np.float32) if rmargin is None
                   else np.asarray(rmargin, np.float32))
        extras = lambda idx: (rsel[idx], rmargin[idx])
    ahap_args = (arr("omega")[ahap_idx], arr("v")[ahap_idx],
                 arr("sigma")[ahap_idx], rho[ahap_idx], *extras(ahap_idx))
    cheap_args = (arr("kind")[other_idx], arr("sigma")[other_idx],
                  cfrac[other_idx], *extras(other_idx))
    return ahap_idx, other_idx, ahap_args, cheap_args


def _program_args(ahap: bool, lanes, jobs, tput, prices, avail, pred,
                  backend: str, collect: bool, fallback, region):
    """(program, positional args) of one kind partition, for both drivers:
    ``lanes`` as :func:`_partition_lane_args` slices them, ``region`` None
    for a flat market or ``(delta_mig, p_od)``. A flat call passes nothing
    the flat program does not take."""
    if ahap:
        return _pool_jobs_ahap, (
            *lanes[:4], jobs, tput, prices, avail, pred, backend, collect,
            fallback, *lanes[4:], *(region or ()))
    return _pool_jobs_cheap, (
        *lanes[:3], jobs, tput, prices, avail, collect, fallback,
        *lanes[3:], *((pred, *region) if region else ()))


def _run_partitioned(pool_arrays, jobs, tput, prices, avail, pred,
                     backend: str, collect: bool, fallback, region):
    """Partition -> dispatch -> scatter-back driver of the single-device
    pool (lane slicing in :func:`_partition_lane_args`)."""
    with TraceAnnotation("sched.pool.lanes"):
        ahap_idx, other_idx, ahap_args, cheap_args = _partition_lane_args(
            pool_arrays, region is not None
        )

    def run(ahap: bool, lane_args):
        # each partition's lanes go over just before its program, so the
        # AHAP program starts before the cheap lanes are sent
        with TraceAnnotation("sched.pool.lanes"):
            counters.h2d(lane_args)
            lane_args = tuple(jnp.asarray(a) for a in lane_args)
        program, args = _program_args(ahap, lane_args, jobs, tput, prices,
                                      avail, pred, backend, collect,
                                      fallback, region)
        with TraceAnnotation("sched.pool.ahap" if ahap else
                             "sched.pool.cheap"):
            return counters.launch(program, *args)

    parts, idxs = [], []
    if ahap_idx.size:
        parts.append(run(True, ahap_args))
        idxs.append(ahap_idx)
    if other_idx.size:
        parts.append(run(False, cheap_args))
        idxs.append(other_idx)
    return _scatter_merge(parts, idxs, axis=1)


def _as_p_od(p_od, n_regions: int):
    """Normalize a per-region on-demand price multiplier: None passes
    through (the flat-od program is traced unchanged), a scalar broadcasts
    to (R,), an (R,) array is taken as-is."""
    if p_od is None:
        return None
    x = p_od if isinstance(p_od, jax.Array) else np.asarray(p_od)
    counters.h2d(x)
    # a program each for the float32 conversion, the reshape and the
    # broadcast, where they change anything
    counters.add("launches", int(x.dtype != np.float32) + int(x.ndim != 1)
                 + int(x.size != n_regions))
    return jnp.broadcast_to(
        jnp.asarray(p_od, jnp.float32).reshape(-1), (n_regions,)
    )


def _region(prices, delta_mig, p_od):
    """The region operands of a pool call: None for flat (J, d_max) market
    rows, ``(delta_mig, p_od)`` for (J, R, d_max) rows — which must bring
    their ``delta_mig``: a default here would silently override the cost a
    RegionalMarket was built with."""
    if np.ndim(prices) != 3:
        if delta_mig is not None or p_od is not None:
            raise ValueError("delta_mig and p_od apply to region-axis "
                             "(J, R, d_max) market rows only")
        return None
    if delta_mig is None:
        raise ValueError("region-axis market rows need delta_mig (the "
                         "market's migration cost in slots)")
    return int(delta_mig), _as_p_od(p_od, np.shape(prices)[1])


def simulate_pool(pool_arrays: dict, j: JobArrays, tput: ThroughputConfig,
                  prices, avail, pred, backend: str = "xla",
                  collect: bool = False, fallback=None):
    """One job's :func:`simulate_pool_jobs`: ``j`` holds scalar leaves,
    prices/avail are (d_max,), pred (d_max, W1MAX, 2); results are (P, ...)
    in the original pool order (pinned against simulator.simulate)."""
    rows = [jnp.asarray(x)[None] for x in (*j, prices, avail, pred)]
    counters.h2d((j, prices, avail, pred))
    counters.add("launches", len(rows))          # a reshape per operand
    out = simulate_pool_jobs(pool_arrays, JobArrays(*rows[:-3]), tput,
                             *rows[-3:], backend, collect, fallback)
    counters.add("launches", len(out))           # a slice per result leaf
    return {k: v[0] for k, v in out.items()}


def simulate_pool_jobs(pool_arrays: dict, jobs: JobArrays, tput: ThroughputConfig,
                       prices, avail, pred, backend: str = "xla",
                       collect: bool = False, fallback=None, *,
                       delta_mig=None, p_od=None):
    """Double vmap: jobs (leading axis) x policy pool -> dict of (J, P, ...).

    ``jobs`` leaves are stacked (J,) arrays; prices/avail: (J, d_max);
    pred: (J, d_max, W1MAX, 2) (see :func:`prepare_inputs`). One XLA call
    per kind-partition simulates the paper's whole Fig. 9/10 workload.
    ``collect=True`` adds the (J, P, T) ``tel_*`` flight-recorder series
    (repro.obs); ``fallback`` (static repro.chaos.FallbackConfig) arms the
    AHAP lanes' online prediction-failure fallback (None — the default —
    is bitwise-pinned to the shipped program).

    **Regions** — (J, R, d_max) prices/avail and (J, R, d_max, W1MAX, 2)
    pred run the region-aware lanes over an R-region market. ``delta_mig``
    is then required: the checkpoint-transfer cost in lost slots (pass
    ``market.delta_mig``). Lanes read their region-selection strategy from
    pool_arrays' ``rsel``/``rmargin`` slots (policy_pool.region_pool;
    absent keys mean every lane stays put). The result gains ``region``
    (the lane's region each slot) and ``migrations`` (completed switches);
    collect adds ``tel_region``/``tel_migration`` (slot sums reconcile
    against ``migrations`` in obs.ledger.migration_reconciliation), and
    the fallback monitor runs per lane. ``p_od`` (scalar or (R,)) scales
    the on-demand price by occupied region (``market.p_od``; multipliers
    of the job's flat ``on_demand_price``). With R == 1 the shared leaves
    are bitwise-identical to the flat market's. ``delta_mig`` or ``p_od``
    with flat rows raises ``ValueError``."""
    return _run_partitioned(
        pool_arrays, jobs, tput, prices, avail, pred, backend, collect,
        fallback, _region(prices, delta_mig, p_od),
    )


def _pad_leading(x, pad: int):
    """Pad axis 0 by repeating the last entry ``pad`` times (dropped from the
    result after the sharded run)."""
    x = _device(x)
    if not pad:
        return x
    # four programs: the last entry's slice, the repeat's broadcast and
    # reshape, the concatenation
    counters.add("launches", 4)
    return jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)], axis=0)


@functools.lru_cache(maxsize=None)
def _sharded_pool_call(mesh, tput, backend: str, delta_mig, ahap: bool,
                       lspec, jspec, ospec, collect: bool = False,
                       fallback=None, has_p_od: bool = False):
    """jit(shard_map)-wrapped runner for one kind partition, cached on the
    static configuration (``collect``, ``fallback``, ``delta_mig`` and
    ``has_p_od`` are part of the key: the telemetry, degradation, region
    and per-region-od programs are different lowerings; a ``delta_mig``
    (None on a flat market) adds the (rsel, rmargin) lane operands,
    ``has_p_od`` a replicated (R,) one).
    The cache is what keeps the sharded path's per-call cost at dispatch
    level: a fresh shard_map closure per call would retrace (and re-lower)
    the whole pool program every invocation — the prime mover of the old
    1000-job sharded-scale regression. The two runners are named apart
    (``jit_sharded_pool_<kind>`` in a device trace)."""
    regional = delta_mig is not None
    n_lane = (4 if ahap else 3) + (2 if regional else 0)

    # flat cheap lanes take no forecasts: pm_ rides along unused
    def local(*ops):
        lanes, (jb, pr_, av_, pm_) = ops[:n_lane], ops[n_lane:n_lane + 4]
        region = None
        if regional:
            region = (delta_mig, ops[n_lane + 4] if has_p_od else None)
        program, args = _program_args(ahap, lanes, jb, tput, pr_, av_, pm_,
                                      backend, collect, fallback, region)
        return program(*args)

    local.__name__ = "sharded_pool_" + ("ahap" if ahap else "cheap")
    from jax.sharding import PartitionSpec

    # the tiny (R,) od-multiplier vector is replicated to every device
    pod_spec = (PartitionSpec(),) if has_p_od else ()
    return jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(lspec,) * n_lane + (jspec,) * 4 + pod_spec,
        out_specs=ospec, check_vma=False,
    ))


def _run_partitioned_sharded(pool_arrays, jobs, tput, prices, avail, pred,
                             backend: str, mesh, *, collect: bool = False,
                             fallback=None, region=None):
    """Sharded twin of :func:`_run_partitioned`: partition by kind on the
    host, then lay each partition's (jobs x lanes) grid over ``mesh``.

    Jobs shard the mesh's job axes; on a 2-D ``("jobs", "lanes")`` pool mesh
    (launch.mesh.make_pool_mesh(shape=(a, b))) each partition's policy-lane
    axis additionally shards over ``"lanes"`` — the kind split happens
    first, so a lane shard is uniformly DP-heavy (AHAP) or uniformly cheap.
    Both axes pad to divisibility by repeating the last entry; padding is
    dropped before the scatter-merge back to pool order. Market data
    (prices/avail/pred) is sharded over jobs and replicated only across the
    lane axis, where every lane shard genuinely needs all of it; a region
    axis rides whole inside it. ``region`` as in :func:`_program_args`."""
    from repro import sharding as shardlib
    from repro.launch.mesh import pool_mesh_job_axes

    jobs_axes, n_jobs_dev, n_lane_dev = pool_mesh_job_axes(mesh)
    delta_mig, p_od = region or (None, None)

    n_jobs = int(np.shape(jobs.workload)[0])
    pad_j = (-n_jobs) % n_jobs_dev
    # resolve the logical axes against the mesh (divisibility always holds
    # after padding; a non-matching mesh degrades to replication)
    rules = {**shardlib.DEFAULT_RULES, "jobs": jobs_axes}
    jspec = shardlib.resolve_spec(("jobs",), (n_jobs + pad_j,), mesh, rules)

    # operand padding and transfer, and the lane split
    with TraceAnnotation("sched.pool.lanes"):
        if pad_j:
            jobs = JobArrays(*[_pad_leading(f, pad_j) for f in jobs])
            prices, avail, pred = (
                _pad_leading(x, pad_j) for x in (prices, avail, pred)
            )
        ahap_idx, other_idx, ahap_args, cheap_args = _partition_lane_args(
            pool_arrays, region is not None
        )
        pr_j, av_j, pm_j = (_device(x) for x in (prices, avail, pred))
        pod_args = () if p_od is None else (_device(p_od),)

    def run_part(ahap: bool, lane_arrays):
        with TraceAnnotation("sched.pool.lanes"):
            p_l = int(np.shape(lane_arrays[0])[0])
            pad_l = (-p_l) % n_lane_dev
            lane_in = tuple(_pad_leading(a, pad_l) for a in lane_arrays)
        lspec = shardlib.resolve_spec(("lanes",), (p_l + pad_l,), mesh, rules)
        ospec = shardlib.resolve_spec(
            ("jobs", "lanes"), (n_jobs + pad_j, p_l + pad_l), mesh, rules
        )
        call = _sharded_pool_call(
            mesh, tput, backend, delta_mig, ahap, lspec, jspec, ospec,
            collect, fallback, p_od is not None,
        )
        with TraceAnnotation("sched.pool.ahap" if ahap else
                             "sched.pool.cheap"):
            out = counters.launch(call, *lane_in, jobs, pr_j, av_j, pm_j,
                                  *pod_args)
            if pad_l:
                counters.add("launches", len(out))
                out = {k: v[:, :p_l] for k, v in out.items()}
        return out

    parts, idxs = [], []
    if ahap_idx.size:
        parts.append(run_part(True, ahap_args))
        idxs.append(ahap_idx)
    if other_idx.size:
        parts.append(run_part(False, cheap_args))
        idxs.append(other_idx)
    out = _scatter_merge(parts, idxs, axis=1)
    if pad_j:
        with TraceAnnotation("sched.pool.merge"):
            counters.add("launches", len(out))
            out = {k: v[:n_jobs] for k, v in out.items()}
    return out


def simulate_pool_jobs_sharded(
    pool_arrays: dict,
    jobs: JobArrays,
    tput: ThroughputConfig,
    prices, avail, pred,
    backend: str = "xla",
    mesh=None,
    collect: bool = False,
    fallback=None,
    *,
    delta_mig=None,
    p_od=None,
):
    """Device-sharded :func:`simulate_pool_jobs`: the (jobs x lanes) grid is
    laid over ``mesh`` (default: repro.launch.mesh.make_pool_mesh over every
    visible device, jobs-only). On a 1-D mesh jobs ride the mesh axis and
    lanes stay whole per device; a 2-D ``("jobs", "lanes")`` mesh
    (``make_pool_mesh(shape=(a, b))``) additionally shards each kind
    partition's lane axis — for small job counts with huge pools the lane
    axis is where the parallelism is. The kind partition happens *before*
    sharding, so each device runs a uniform DP-heavy-AHAP or cheap lane
    slice of its job shard (load balance by construction). Jobs and lanes
    that do not divide their mesh axis are padded by repeating the last
    entry; padding is dropped from the result. Region-axis inputs
    (``delta_mig``, ``p_od``) as in :func:`simulate_pool_jobs`: the small
    region axis rides whole per device inside the (J, R, T) market tensors
    and ``p_od``, when set, is replicated to every device.

    Per-(job, lane) cells are independent and every op is elementwise over
    both axes, so the result is BITWISE-equal to ``simulate_pool_jobs``
    (pinned in tests/test_sharded_pool.py for the jobs, lanes and 2-D
    layouts, flat and regional). With one visible device this falls
    through to ``simulate_pool_jobs`` itself. ``collect=True`` adds the
    (J, P, T) ``tel_*`` flight-recorder series (repro.obs); telemetry
    shards like the allocation histories, so sharded collect runs stay
    bitwise-equal to unsharded ones. ``fallback`` (static
    repro.chaos.FallbackConfig) arms the AHAP lanes' online
    prediction-failure fallback — the monitor is per-(job, lane)-cell
    local, so sharded fallback runs stay bitwise-equal to unsharded ones
    too.
    """
    from repro.launch.mesh import make_pool_mesh

    if mesh is None:
        mesh = make_pool_mesh()
    if int(np.prod(mesh.devices.shape)) == 1:
        return simulate_pool_jobs(
            pool_arrays, jobs, tput, prices, avail, pred, backend=backend,
            collect=collect, fallback=fallback, delta_mig=delta_mig,
            p_od=p_od,
        )
    return _run_partitioned_sharded(
        pool_arrays, jobs, tput, prices, avail, pred, backend, mesh,
        collect=collect, fallback=fallback,
        region=_region(prices, delta_mig, p_od),
    )


def stack_jobs(jobs) -> JobArrays:
    return JobArrays(*[
        jnp.stack([jnp.asarray(getattr(JobArrays.of(j), f)) for j in jobs])
        for f in JobArrays._fields
    ])


def slice_jobs(jobs: JobArrays, start: int, stop: int) -> JobArrays:
    """Job-axis slice of stacked (K,) JobArrays leaves — the unit of
    core.engine's job-chunked streaming mode."""
    return JobArrays(*[f[start:stop] for f in jobs])


def concat_jobs(parts) -> JobArrays:
    """Concatenate stacked JobArrays along the job axis (host numpy leaves)
    — the inverse of repeated :func:`slice_jobs`; how the scenario grid
    stacks per-regime job blocks regime-major onto one jobs axis."""
    parts = list(parts)
    if len(parts) == 1:
        return parts[0]
    return JobArrays(*[
        np.concatenate([np.asarray(getattr(p, f)) for p in parts])
        for f in JobArrays._fields
    ])


def unstack_jobs(jobs: JobArrays):
    """Stacked (K,) JobArrays -> list of JobConfig (host scalars) — the
    inverse of :func:`stack_jobs`, for python-reference paths that need
    per-job configs (e.g. the pre-engine normalize_utility loop)."""
    n = int(np.shape(jobs.workload)[0])
    rows = [np.asarray(f) for f in jobs]
    return [
        JobConfig(
            workload=float(rows[0][k]), deadline=int(rows[1][k]),
            n_min=int(rows[2][k]), n_max=int(rows[3][k]),
            value=float(rows[4][k]), gamma=float(rows[5][k]),
            on_demand_price=float(rows[6][k]),
        )
        for k in range(n)
    ]


def prepare_inputs(trace, pred_matrix, d_max: int):
    """Pad/trim a market's rows and a prediction matrix to ``d_max`` slots,
    as jnp arrays: (d_max,) prices/avail and a (d_max, W1MAX, 2) forecast
    stack for a single-market Trace; the same with a leading region axis
    (R, ...) for a RegionalMarket. ``pred_matrix=None`` broadcasts the
    observed present over the forecast window."""
    prices = np.asarray(trace.prices)[..., :d_max]
    avail = np.asarray(trace.avail)[..., :d_max]
    if pred_matrix is None:
        pm = np.zeros(prices.shape + (W1MAX, 2), np.float32)
        pm[..., 0] = prices[..., None]
        pm[..., 1] = avail[..., None]
    else:
        pm = np.asarray(pred_matrix[..., :d_max, :W1MAX, :], np.float32)
        if pm.shape[-2] < W1MAX:
            pad = np.repeat(pm[..., -1:, :], W1MAX - pm.shape[-2], axis=-2)
            pm = np.concatenate([pm, pad], axis=-2)
    return (jnp.asarray(prices, jnp.float32), jnp.asarray(avail, jnp.int32),
            jnp.asarray(pm))
