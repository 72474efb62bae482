"""Exact solver for the CHC window problem (Eq. 10).

    max_{n^o, n^s}  Ṽ(Z_t-1 + alpha * units) - sum_tau (n^o p^o + n^s p^s_tau)

Structure: with H linear (beta=0, the paper's evaluation setting), a decision
is just a multiset of (slot, instance) *units*, each contributing alpha
workload at its own price; per-slot supply is min(avail, Nmax) spot units at
p^s plus on-demand units at p^o, capped at Nmax total. The optimal multiset
is a prefix of the price-sorted unit list — BUT Ṽ is piecewise-linear and
NOT concave (slope jumps up where completion crosses gamma*d), so greedy
marginal stopping is wrong. We instead evaluate the objective at *every*
prefix length via cumsum and take the argmax: exact, O(W log W), fully
vectorizable (vmap/scan safe — used inside the policy-pool simulator).

Slots beyond the job deadline get infinite price (the paper only schedules
up to d; the termination configuration handles the rest). An N^min repair
pass rounds up/zeroes out violating slots (exactness for N^min=1; checked
against brute force in tests for N^min>1).

Backends (``backend=`` on :func:`solve_window`):

``"xla"``            default; min-plus DP as tn+1 statically-shifted slices
                     of a padded cost vector (no gathers — much faster on
                     CPU/TPU than the seed formulation, bitwise-identical
                     results).
``"xla-gather"``     the seed formulation (per-step (U+1, tn+1) gather +
                     argmin). Kept as the benchmark baseline.
``"pallas"``         fused Pallas kernel (repro.kernels.window_dp): DP,
                     objective argmax and backtrack in one kernel.
``"pallas-interpret"`` same kernel through the Pallas interpreter (CPU).

:func:`solve_window_batch` solves a whole lane batch in ONE call — a single
(B, w1, tn+1) shifted-slice DP on the XLA backends, a single kernel launch
on the Pallas backends. It is what the pool simulator issues per scan slot.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import JobConfig, ThroughputConfig
from repro.core.job import tilde_value

_BIG = 1.0e9

# Deterministic near-tie resolution. The paper's round constants (p^o = 1,
# value/deadline ratios) make "the marginal unit is exactly free" a
# structural occurrence, and at an exact f32 tie the argmax is at the mercy
# of compilation: XLA may or may not emit FMA for the cost/gain products
# depending on the surrounding program, so two jit programs of this very
# file can disagree by 1 ulp and pick opposite sides of the tie (observed:
# the python-policy jit entry vs the fleet scan). Biasing the gain by
# -TIE_EPS per unit makes every near-tie (true marginal value < TIE_EPS)
# resolve to FEWER units in every compilation — 2^-10 is exact in f32
# (no new rounding), ~2 orders above FMA noise at the objective's scale,
# and ~2 orders below any real marginal value. The reported objective is
# un-biased before returning, so achieved-utility pins are unaffected.
TIE_EPS = np.float32(2.0 ** -10)

BACKENDS = ("xla", "xla-gather", "pallas", "pallas-interpret")


def _unit_cost_table(job, tput, z0, slots_to_deadline, prices, avail, p_o, tn):
    """Shared scaffolding for every backend.

    Returns (slot_cost (w1, tn+1), spot_units (w1,), gain (U+1,)) where
    slot_cost[tau, k] is the cheapest cost of buying k units in slot tau
    (spot-first split; infeasible k priced out with _BIG) and gain[u] is
    Ṽ(z0 + alpha * u).
    """
    w1 = prices.shape[0]
    nmax = job.n_max                       # may be a tracer

    in_horizon = jnp.arange(w1) < slots_to_deadline
    spot_ok = (prices <= p_o) & in_horizon
    spot_units = jnp.where(spot_ok, jnp.minimum(avail, nmax), 0)  # (w1,)

    ks = jnp.arange(tn + 1)[None, :].astype(jnp.float32)  # (1, tn+1)
    n_sp = jnp.minimum(ks, spot_units[:, None].astype(jnp.float32))
    slot_cost = n_sp * prices[:, None] + (ks - n_sp) * p_o
    feasible_k = (ks == 0) | (
        (ks >= job.n_min) & (ks <= nmax) & in_horizon[:, None]
    )
    slot_cost = jnp.where(feasible_k, slot_cost, _BIG)

    u_grid = jnp.arange(w1 * tn + 1)
    zs = jnp.asarray(z0, jnp.float32) + tput.alpha * u_grid.astype(jnp.float32)
    gain = tilde_value(job, tput, zs) - TIE_EPS * u_grid.astype(jnp.float32)
    return slot_cost, spot_units, gain


def _dp_step_shifted(C, row, tn: int, U: int):
    """One min-plus DP step as tn+1 statically-shifted adds (no gather).

    Bitwise-identical to the gather formulation: the candidate values
    C[u-k] + row[k] are the same floats, min/argmin are exact, and the
    running `<` comparison keeps the smallest k on ties exactly like
    jnp.argmin."""
    padded = jnp.concatenate([jnp.full((tn,), _BIG, C.dtype), C])
    best = C + row[0]
    bestk = jnp.zeros(C.shape, jnp.int32)
    for k in range(1, tn + 1):
        cand = jax.lax.slice(padded, (tn - k,), (tn - k + U + 1,)) + row[k]
        take = cand < best
        best = jnp.where(take, cand, best)
        bestk = jnp.where(take, k, bestk)
    return best, bestk


def _dp_step_shifted_batch(C, row, tn: int, U: int):
    """Lane-batched twin of :func:`_dp_step_shifted`: C is (B, U+1), row is
    (B, tn+1). Same candidate floats, same running `<` tie-breaking — bitwise
    identical per lane to the scalar step (pinned in tests)."""
    b = C.shape[0]
    padded = jnp.concatenate(
        [jnp.full((b, tn), _BIG, C.dtype), C], axis=1
    )
    best = C + row[:, 0:1]
    bestk = jnp.zeros(C.shape, jnp.int32)
    for k in range(1, tn + 1):
        cand = jax.lax.slice(
            padded, (0, tn - k), (b, tn - k + U + 1)
        ) + row[:, k : k + 1]
        take = cand < best
        best = jnp.where(take, cand, best)
        bestk = jnp.where(take, k, bestk)
    return best, bestk


def _dp_step_gather(C, row, tn: int, U: int):
    """Seed formulation: per-step (U+1, tn+1) candidate matrix via gather."""
    u_grid = jnp.arange(U + 1)
    uk = u_grid[:, None] - jnp.arange(tn + 1)[None, :]
    prevC = jnp.where(uk >= 0, C[jnp.clip(uk, 0, U)], _BIG)
    cand = prevC + row[None, :]
    choice = jnp.argmin(cand, axis=1)
    return jnp.min(cand, axis=1), choice


def _solve_xla(slot_cost, gain, tn: int, *, gather: bool):
    """DP forward + objective argmax + backtrack in plain XLA ops."""
    w1 = slot_cost.shape[0]
    U = w1 * tn
    step = _dp_step_gather if gather else _dp_step_shifted

    def dp_step(C, row):
        return step(C, row, tn, U)

    C0 = jnp.where(jnp.arange(U + 1) == 0, 0.0, _BIG)
    C, choices = jax.lax.scan(dp_step, C0, slot_cost)  # choices: (w1, U+1)

    obj = gain - C
    obj = jnp.where(C < _BIG / 2, obj, -jnp.inf)
    u_star = jnp.argmax(obj)

    # backtrack: slots in reverse order
    def back_step(u, choice_row):
        k = choice_row[u]
        return u - k, k

    _, k_rev = jax.lax.scan(back_step, u_star, choices, reverse=True)
    return k_rev.astype(jnp.int32), obj[u_star]


def _solve_xla_batch(slot_cost, gain, tn: int):
    """Lane-batched DP forward + objective argmax + backtrack: one call for a
    (B, w1, tn+1) table instead of vmap-per-lane. Slots ride the scan axis,
    lanes the array batch axis."""
    b, w1, _ = slot_cost.shape
    U = w1 * tn

    def dp_step(C, row):
        return _dp_step_shifted_batch(C, row, tn, U)

    C0 = jnp.broadcast_to(
        jnp.where(jnp.arange(U + 1) == 0, 0.0, _BIG), (b, U + 1)
    )
    # scan over slots: xs leading axis must be w1
    C, choices = jax.lax.scan(
        dp_step, C0, jnp.swapaxes(slot_cost, 0, 1)
    )  # choices: (w1, B, U+1)

    obj = gain - C
    obj = jnp.where(C < _BIG / 2, obj, -jnp.inf)
    u_star = jnp.argmax(obj, axis=1)  # (B,) smallest-u on ties, like argmax

    # Each row's k at u is read by a masked sum over the unit axis, not a
    # per-row gather: one int32 term is left unmasked, so the sum is that
    # value, and the read is dense (a gather of single elements cost ~8 ns
    # each on a TPU v5e).
    # u stays in [0, U]: k = 0 is free in every slot, so u_star is reachable,
    # and a reachable state only steps to reachable states.
    units = jnp.arange(U + 1)

    def back_step(u, choice_row):
        k = jnp.sum(jnp.where(units == u[:, None], choice_row, 0), axis=1,
                    dtype=choice_row.dtype)
        return u - k, k

    _, k_rev = jax.lax.scan(back_step, u_star, choices, reverse=True)
    n_tot = jnp.swapaxes(k_rev, 0, 1).astype(jnp.int32)  # (B, w1)
    # obj[u_star] is the row's max: argmax picks a first maximum (-inf rows too)
    return n_tot, jnp.max(obj, axis=1)


@jax.named_scope("window_dp")
def solve_window_batch(
    job: JobConfig,
    tput: ThroughputConfig,
    z0,                         # (B,) progress per lane
    slots_to_deadline,          # (B,) per-lane window cut-off
    prices,                     # (B, w1) per-lane predicted spot prices
    avail,                      # (B, w1) per-lane predicted availability
    p_o,
    table_n: int,               # static unit-table width (required: job.n_max
                                # may be a tracer in the vmapped simulator)
    backend: str = "xla",
):
    """Batched :func:`solve_window`: ONE DP call for a whole lane batch.

    This is the in-scan entry point of the pool simulator — each scan slot
    issues a single (B, w1, tn+1) solve across all AHAP lanes instead of
    relying on vmap's per-lane grid batching. The Pallas backends hand the
    full batch to one ``window_dp`` kernel launch; the XLA backends run the
    lane-batched shifted-slice DP. Bitwise-equal per lane to
    ``jax.vmap(solve_window)`` (pinned in tests/test_window_dp_kernel.py).

    ``job`` fields (and ``p_o``) may also be (B,) vectors — one job per
    batch row, the fleet engine's shape — in which case the unit table is
    built per row. Every op in ``_unit_cost_table`` is elementwise in the
    job fields, so the shared-job lane path is unchanged bitwise.

    Returns (n_o (B, w1), n_s (B, w1), objective (B,)). Its ops carry the
    ``window_dp`` scope in their names, so a device trace can tell the
    DP's time inside the AHAP scan.
    """
    assert backend in BACKENDS, backend
    prices = jnp.asarray(prices, jnp.float32)
    avail = jnp.asarray(avail, jnp.int32)
    tn = int(table_n)
    assert tn > 0, "solve_window_batch needs a static table_n"

    if jnp.asarray(job.workload).ndim or jnp.asarray(p_o).ndim:
        b = prices.shape[0]
        bc = lambda x: jnp.broadcast_to(jnp.asarray(x), (b,))

        def _row_table(z, std, pr, av, wl, dl, nmin, nmax, val, gam, po):
            row_job = JobConfig(workload=wl, deadline=dl, n_min=nmin,
                                n_max=nmax, value=val, gamma=gam,
                                on_demand_price=po)
            return _unit_cost_table(row_job, tput, z, std, pr, av, po, tn)

        slot_cost, spot_units, gain = jax.vmap(_row_table)(
            jnp.asarray(z0, jnp.float32), jnp.asarray(slots_to_deadline),
            prices, avail, bc(job.workload), bc(job.deadline), bc(job.n_min),
            bc(job.n_max), bc(job.value), bc(job.gamma), bc(p_o),
        )
    else:
        slot_cost, spot_units, gain = jax.vmap(
            lambda z, std, pr, av: _unit_cost_table(
                job, tput, z, std, pr, av, p_o, tn
            )
        )(jnp.asarray(z0, jnp.float32), jnp.asarray(slots_to_deadline),
          prices, avail)

    if backend in ("pallas", "pallas-interpret"):
        from repro.kernels.window_dp import window_dp

        n_tot, obj_star = window_dp(
            slot_cost, gain, interpret=(backend == "pallas-interpret")
        )
    elif backend == "xla":
        n_tot, obj_star = _solve_xla_batch(slot_cost, gain, tn)
    else:  # "xla-gather": keep the seed formulation, vmapped per lane
        n_tot, obj_star = jax.vmap(
            lambda c, g: _solve_xla(c, g, tn, gather=True)
        )(slot_cost, gain)

    n_s = jnp.minimum(n_tot, spot_units).astype(jnp.int32)
    n_o = n_tot - n_s
    obj_star = obj_star + TIE_EPS * jnp.sum(n_tot, axis=1).astype(jnp.float32)
    return n_o, n_s, obj_star


def solve_window(
    job: JobConfig,
    tput: ThroughputConfig,
    z0,
    slots_to_deadline,          # d - t: how many window slots are before d
    prices,                     # (w1,) predicted spot prices  [t..t+w]
    avail,                      # (w1,) predicted spot availability
    p_o: float,
    table_n: int = 0,           # static unit-table width (0 -> job.n_max)
    backend: str = "xla",
):
    """Returns (n_o (w1,), n_s (w1,), predicted_objective scalar).

    jnp-traceable, including *dynamic* job fields (n_max/n_min/L may be
    tracers inside the vmapped simulator) — only w1, table_n and backend
    set shapes / dispatch.
    """
    assert backend in BACKENDS, backend
    prices = jnp.asarray(prices, jnp.float32)
    avail = jnp.asarray(avail, jnp.int32)
    tn = int(table_n) if table_n else int(job.n_max)

    slot_cost, spot_units, gain = _unit_cost_table(
        job, tput, z0, slots_to_deadline, prices, avail, p_o, tn
    )

    if backend in ("pallas", "pallas-interpret"):
        from repro.kernels.window_dp import window_dp

        n_tot_b, obj_b = window_dp(
            slot_cost[None], gain[None], interpret=(backend == "pallas-interpret")
        )
        n_tot, obj_star = n_tot_b[0], obj_b[0]
    else:
        n_tot, obj_star = _solve_xla(
            slot_cost, gain, tn, gather=(backend == "xla-gather")
        )

    n_s = jnp.minimum(n_tot, spot_units).astype(jnp.int32)
    n_o = n_tot - n_s
    obj_star = obj_star + TIE_EPS * jnp.sum(n_tot).astype(jnp.float32)
    return n_o, n_s, obj_star


@functools.lru_cache(maxsize=64)
def _jitted_solver(job: JobConfig, tput: ThroughputConfig, w1: int, p_o: float):
    fn = lambda z0, std, prices, avail: solve_window(
        job, tput, z0, std, prices, avail, p_o
    )
    return jax.jit(fn)


def solve_window_numpy(job, tput, z0, slots_to_deadline, prices, avail, p_o):
    """Eager wrapper (python policies). jitted + cached per (job, tput, w1)."""
    prices = np.asarray(prices, np.float32)
    fn = _jitted_solver(job, tput, len(prices), float(p_o))
    n_o, n_s, obj = fn(
        jnp.float32(z0), jnp.int32(slots_to_deadline),
        prices, np.asarray(avail, np.int32),
    )
    return np.asarray(n_o), np.asarray(n_s), float(obj)


def brute_force_window(job, tput, z0, slots_to_deadline, prices, avail, p_o,
                       beta_exact: bool = True):
    """Exponential-time exact reference (tests only): enumerates per-slot
    totals in {0} u [Nmin, Nmax], spot-first split."""
    prices = np.asarray(prices, float)
    avail = np.asarray(avail, int)
    w1 = len(prices)
    choices = [0] + list(range(job.n_min, job.n_max + 1))
    best = (-np.inf, None)

    def rec(tau, z, cost, plan):
        nonlocal best
        if tau == w1:
            u = float(tilde_value(job, tput, z)) - cost
            if u > best[0]:
                best = (u, list(plan))
            return
        if tau >= slots_to_deadline:
            rec(w1, z, cost, plan + [0] * (w1 - tau))
            return
        for n in choices:
            ns = min(n, avail[tau]) if prices[tau] <= p_o else 0
            no = n - ns
            c = ns * prices[tau] + no * p_o
            h = tput.alpha * n + (tput.beta if n > 0 else 0.0)
            rec(tau + 1, z + h, cost + c, plan + [n])

    rec(0, float(z0), 0.0, [])
    return best
