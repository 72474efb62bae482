"""Device-resident fleet-scale multi-job contention engine.

The paper's Sec. III-A extension — jobs arriving over time and competing
for one finite spot pool under least-slack-first arbitration — as a single
``lax.scan`` over market slots with the job axis batched (and optionally
sharded over the pool mesh). Semantics are pinned bit-for-bit-in-spirit to
the numpy parity oracle ``core.multi_job.MultiJobScheduler``:

  * **demand phase** — every live job's policy decides against the FULL
    slot supply. The AHAP rule (``fast_sim._ahap_rule_batch``, the
    slot-major batched window DP over per-job local clocks ``t -
    arrival``) and the five cheap kinds' vectorized rules
    (``fast_sim._cheap_rules``) all run over every row, and each row keeps
    the demand of its own kind;
  * **waterfall phase** — spot demand is granted least-slack-first as a
    sort + cumulative-supply clip instead of a Python loop: with demands
    sorted by the float32 slack key (job-id tie-break), ``grant_i =
    clip(S - (cumsum(d)_i - d_i), 0, d_i)`` makes cumulative grants equal
    ``min(cumsum(d), S)`` — integer-exact, identical to the oracle's
    sequential residual loop;
  * **execute phase** — ``fast_sim._execute`` on the granted spot (its
    internal feasibility clip reduces to exactly the oracle's post-grant
    N^min top-up), with arrivals/retirements gated by ``t - arrival``
    masks so jobs stream in and out without host round-trips.

Rows stay in submission order and the policy kind is a mask, not a
layout: the program's shapes depend on the job count and the horizon
alone, so one compiled program serves every policy mix (the admitted AHAP
count changes with every EG draw). Every row's decision is computed from
that row's inputs only, so a row's results are bitwise what a kind-blocked
program gives it.

Sharding lays the job axis over the pool mesh's ``"jobs"`` axis (2-D
meshes replicate over ``"lanes"``: the fleet has no lane axis). The rows
pad to device divisibility with ``arrival = T`` sentinel jobs (never
live, demand 0) and each device holds one contiguous slice; the waterfall
runs on an ``all_gather`` of (demand, slack), every device granting the
identical global order and keeping its own slice. Row ids are global row
positions, so real jobs keep their submission ids and pads sort after
them on a tie; pads demand nothing, so they cannot perturb real grants in
any sort position: sharded results are bitwise-equal to the single-device
scan.

The host side marks its steps with ``sched.fleet.*`` profiler spans
(``prep``: policy rows, market clamp and layout; ``scan``: the dispatch;
``gather``: dropping the sharded layout's pad rows, empty on one device)
and counts its launches, transfers and rows in ``repro.obs.counters``.

Per-job policy rows come from the EG selector weights that
``engine.simulate_and_select`` produces (``policy_rows_from_weights`` /
``SelectionResult.admission_rows``), closing the select -> admit loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import fast_sim
from repro.core.fast_sim import VMAX, W1MAX, JobArrays
from repro.core.policy_pool import KIND_AHAP
from repro.obs import counters

_POLICY_KEYS = ("kind", "omega", "v", "sigma", "rho", "cfrac")


# ---------------------------------------------------------------------------
# Least-slack-first waterfall
# ---------------------------------------------------------------------------

def _waterfall(demand, slack, ids, supply):
    """Grant ``demand`` (i32) in ascending ``(slack, id)`` order against a
    scalar ``supply``. Cumulative grants equal ``min(cumsum(demand),
    supply)`` — the vectorized form of "each job takes ``min(demand,
    residual)``" — so the result is integer-exact, not an approximation."""
    order = jnp.lexsort((ids, slack))
    d_sorted = demand[order]
    cum = jnp.cumsum(d_sorted)
    g_sorted = jnp.clip(supply - (cum - d_sorted), 0, d_sorted)
    return jnp.zeros_like(demand).at[order].set(g_sorted)


def _demand_rank(demand, slack, ids):
    """Flight-recorder companion to :func:`_waterfall`: each job's position
    in the demanders-only grant order (-1 for jobs demanding nothing this
    slot). Sorting demanders first (extra ``demand <= 0`` key ahead of the
    same ``(slack, id)`` keys) keeps demander positions identical whether
    or not zero-demand jobs — including the sharded path's sentinel pads,
    which never demand — are present, so sharded and unsharded collect
    runs agree bitwise. Only traced when ``collect=True``."""
    n = ids.shape[0]
    order = jnp.lexsort((ids, slack, (demand <= 0).astype(jnp.int32)))
    pos = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    return jnp.where(demand > 0, pos, -1)


# ---------------------------------------------------------------------------
# The fleet scan (runs whole on one device, or per shard under shard_map)
# ---------------------------------------------------------------------------

_TEL_FLEET = ("tel_demand", "tel_grant", "tel_slack", "tel_rank",
              "tel_starved")


def _fleet_scan(pol, jobs, arrivals, tput, prices, avail, pred,
                backend: str, axis_name: Optional[str] = None,
                collect: bool = False, fallback=None):
    """One ``lax.scan`` over market slots for a fleet (shard).

    ``jobs``/``arrivals`` are (Jl,) leaves in submission order (a shard's
    contiguous slice of the global rows under ``shard_map``); ``pol`` holds
    the per-job policy rows in the same order. Row ``i`` of the shard is
    global row ``shard * Jl + i``, which is also its waterfall tie-break
    id. ``prices``/``avail``/``pred`` are the full shared market ((T,),
    (T,), (T, W1MAX, 2)); the present-slot forecast row is pre-clamped to
    the pool supply by the callers. Under ``shard_map``
    (``axis_name="jobs"``) the waterfall all-gathers (demand, slack) so
    every shard grants the identical global order.

    Every row runs the AHAP rule and the cheap rules; ``pol["kind"]``
    picks which demand the row keeps, so no shape depends on the mix.
    ``collect`` (static) appends the flight-recorder series to the scan
    ys: the shared ``fast_sim._TEL_SLOTS`` slot telemetry (preemption =
    the waterfall grant fell below last slot's allocation) plus the
    ``_TEL_FLEET`` waterfall series (demand vs grant, slack, demanders-only
    grant rank, starvation). The False branch traces none of it.

    ``fallback`` (static repro.chaos.FallbackConfig, or None) arms the
    per-job prediction-health monitor for AHAP jobs: a per-job
    realized-forecast-error EWMA over the shared market forecasts, updated
    only once a job has arrived, switches that job's demand to the
    prediction-free AHANP rule while above threshold (AHANP's "previous
    availability" is the shifted supply ``sup_prev``, the convention the
    cheap AHANP jobs already use). With ``collect`` also on, the per-job
    ``fast_sim._TEL_FALLBACK`` series join the ys (all-zero for the other
    kinds, which consume no predictions).
    """
    prices = jnp.asarray(prices, jnp.float32)
    av_i = jnp.asarray(avail).astype(jnp.int32)
    dmax = prices.shape[0]
    n_jobs = arrivals.shape[0]
    ts = jnp.arange(dmax)
    kind = pol["kind"]
    is_ahap = kind == KIND_AHAP
    # AHANP observes last slot's availability; in the fleet every job sees
    # the shared pool, so the "previous avail" is just the shifted supply
    # (a job's first live slot sees the current supply, like the python
    # policy's first decide).
    sup_prev = jnp.concatenate([av_i[:1], av_i[:-1]])

    fb_on = fallback is not None
    if fb_on:
        fb_thr = jnp.float32(fallback.threshold)
        prev1 = fast_sim._fallback_prev1(pred)            # (T, 2)

    jcfg = fast_sim._job_cfg(jobs)
    # scan-invariant AHAP scaffolding, slot-major like _simulate_lanes_ahap,
    # but on per-job local clocks t - arrival (pre-arrival rows are
    # garbage-but-finite; the plans-validity mask k <= local_t in
    # _ahap_rule_batch keeps them out of every average)
    pr, thr_s, z_exp_end, eff_slots = jax.vmap(
        lambda t, pm: jax.vmap(
            lambda jr, w, s, r, a: fast_sim._ahap_precompute(
                jr, w, s, r, t - a, pm
            )
        )(jobs, pol["omega"], pol["sigma"], pol["rho"], arrivals)
    )(ts, pred)

    if axis_name is None:
        start = 0
        ids = jnp.arange(n_jobs, dtype=jnp.int32)
        ids_all = ids
    else:
        start = jax.lax.axis_index(axis_name) * n_jobs
        ids = start + jnp.arange(n_jobs, dtype=jnp.int32)
        ids_all = jax.lax.all_gather(ids, axis_name, tiled=True)

    h_max = tput.alpha * jobs.n_max.astype(jnp.float32) + tput.beta

    def step(carry, xs):
        if fb_on:
            z, n_prev, cost, done, T, plans, err = carry
            price, sup, sup_p, t, pr_t, thr_t, zee_t, eff_t, p1_t = xs
        else:
            z, n_prev, cost, done, T, plans = carry
            price, sup, sup_p, t, pr_t, thr_t, zee_t, eff_t = xs
        lt = t - arrivals
        live = (lt >= 0) & (lt < jobs.deadline) & ~done

        # ---- demand phase: every policy decides at the FULL supply
        ah_o, ah_s, plans = fast_sim._ahap_rule_batch(
            jcfg, jobs, tput, pol["v"], backend, z, lt, price, sup, plans,
            pr_t, thr_t, zee_t, eff_t,
        )
        pa = jnp.where(lt >= 1, sup_p, sup)
        conds, cand_o, cand_s = fast_sim._cheap_rules(
            kind, jobs, tput, pol["sigma"], pol["cfrac"], z, lt, price, sup,
            n_prev, pa)
        if fb_on:
            # the monitor only accumulates once the job is watching the
            # market (arrived); the shared error sample is scalar
            err = jnp.where(
                lt >= 0,
                fast_sim._fallback_error(fallback, err, price, sup, p1_t),
                err,
            )
            fb = is_ahap & (err > fb_thr)
            # AHANP is the first of the cheap candidates
            ah_o = jnp.where(fb, cand_o[0], ah_o)
            ah_s = jnp.where(fb, cand_s[0], ah_s)
        sel = [is_ahap, *conds()]
        d_o = jnp.select(sel, [ah_o, *cand_o])
        d_s = jnp.select(sel, [ah_s, *cand_s])
        # demand clip against the full pool; dead jobs demand nothing
        d_s = jnp.clip(d_s, 0, jnp.minimum(sup, jobs.n_max))
        d_o = jnp.clip(d_o, 0, jobs.n_max - d_s)
        d_s = jnp.where(live, d_s, 0)
        d_o = jnp.where(live, d_o, 0)

        # ---- waterfall phase: least-slack-first grants (global order)
        slack = ((arrivals + jobs.deadline - t).astype(jnp.float32)
                 - jnp.maximum(jobs.workload - z, 0.0) / h_max)
        if axis_name is None:
            grant = _waterfall(d_s, slack, ids, sup)
            if collect:
                rank = _demand_rank(d_s, slack, ids)
        else:
            d_all = jax.lax.all_gather(d_s, axis_name, tiled=True)
            s_all = jax.lax.all_gather(slack, axis_name, tiled=True)
            g_all = _waterfall(d_all, s_all, ids_all, sup)
            grant = jax.lax.dynamic_slice(g_all, (start,), (n_jobs,))
            if collect:
                r_all = _demand_rank(d_all, s_all, ids_all)
                rank = jax.lax.dynamic_slice(r_all, (start,), (n_jobs,))

        # ---- execute phase: local clock, pre-arrival masked to inactive
        mt = jnp.where(lt >= 0, lt, jobs.deadline)
        n_prev0 = n_prev
        z, n_prev, cost, done, T, n_o, n_s, active = fast_sim._execute(
            jobs, tput, z, n_prev, cost, done, T, mt, d_o, grant, price,
            grant,
        )
        ys = (n_o, n_s)
        if collect:
            ys = ys + fast_sim._slot_telemetry(
                jobs, n_prev0, z, n_o, n_s, active, price, grant
            ) + (d_s, grant, jnp.where(live, slack, 0.0), rank,
                 live & (d_s > 0) & (grant < d_s))
            if fb_on:
                ys = ys + (fb, jnp.where(is_ahap, err, 0.0))
        new_carry = (z, n_prev, cost, done, T, plans)
        if fb_on:
            new_carry = new_carry + (err,)
        return new_carry, ys

    init = (
        jnp.zeros((n_jobs,), jnp.float32), jnp.zeros((n_jobs,), jnp.int32),
        jnp.zeros((n_jobs,), jnp.float32), jnp.zeros((n_jobs,), jnp.bool_),
        jnp.zeros((n_jobs,), jnp.float32),
        jnp.zeros((n_jobs, VMAX, W1MAX, 2), jnp.float32),
    )
    xs = (prices, av_i, sup_prev, ts, pr, thr_s, z_exp_end, eff_slots)
    if fb_on:
        init = init + (jnp.zeros((n_jobs,), jnp.float32),)
        xs = xs + (prev1,)
    (z, _, cost, done, T, *_rest), ys = jax.lax.scan(step, init, xs)
    out = fast_sim._finalize(
        jcfg, jobs, tput, z, cost, done, T,
        jnp.swapaxes(ys[0], 0, 1), jnp.swapaxes(ys[1], 0, 1),
    )
    if collect:
        keys = fast_sim._TEL_SLOTS + _TEL_FLEET + (
            fast_sim._TEL_FALLBACK if fb_on else ())
        for key, hist in zip(keys, ys[2:]):
            out[key] = jnp.swapaxes(hist, 0, 1)
    return out


@functools.partial(jax.jit, static_argnames=(
    "tput", "backend", "collect", "fallback"))
def _fleet_call(pol, jobs, arrivals, tput, prices, avail, pred,
                backend: str, collect: bool = False, fallback=None):
    return _fleet_scan(pol, jobs, arrivals, tput, prices, avail, pred,
                       backend, collect=collect, fallback=fallback)


@functools.lru_cache(maxsize=None)
def _sharded_fleet_call(mesh, tput, backend: str, collect: bool = False,
                        fallback=None):
    """jit(shard_map)-wrapped fleet runner, cached on the static
    configuration (same reasoning as fast_sim._sharded_pool_call: a fresh
    shard_map closure per call would re-lower the whole program)."""
    from jax.sharding import PartitionSpec as P

    jspec, rspec = P("jobs"), P()

    def sharded_fleet_scan(pol, jobs, arrivals, prices, avail, pred):
        return _fleet_scan(pol, jobs, arrivals, tput, prices, avail, pred,
                           backend, axis_name="jobs", collect=collect,
                           fallback=fallback)

    return jax.jit(jax.shard_map(
        sharded_fleet_scan, mesh=mesh,
        in_specs=(jspec, jspec, jspec, rspec, rspec, rspec),
        out_specs=jspec, check_vma=False,
    ))


# ---------------------------------------------------------------------------
# Host-side prep: policy rows, market tensors, layout
# ---------------------------------------------------------------------------

def _norm_rows(pool_rows):
    """Per-job policy rows as host arrays with engine dtypes + defaults."""
    kind = np.asarray(pool_rows["kind"], np.int32)
    n = kind.shape[0]
    rows = {
        "kind": kind,
        "omega": np.asarray(pool_rows.get("omega", np.zeros(n)), np.int32),
        "v": np.maximum(
            np.asarray(pool_rows.get("v", np.ones(n)), np.int32), 1),
        "sigma": np.asarray(pool_rows.get("sigma", np.zeros(n)), np.float32),
        "rho": np.asarray(pool_rows.get("rho", np.ones(n)), np.float32),
        "cfrac": np.asarray(pool_rows.get("cfrac", np.zeros(n)), np.float32),
    }
    return rows, n


def _prepare_market(prices, avail, pred):
    """f32/i-typed market tensors with the oracle's present-slot clamp:
    ``pred[t, 0, 1] <- min(pred[t, 0, 1], avail[t])`` (the pool caps what
    the present slot can deliver; future rows stay the global forecast).
    ``pred=None`` falls back to a persistence forecast (present price and
    supply repeated over the horizon)."""
    prices = np.asarray(prices, np.float32)
    avail = np.asarray(avail)
    dmax = prices.shape[0]
    if pred is None:
        base = np.stack([prices, avail.astype(np.float32)], axis=-1)
        pred = np.broadcast_to(base[:, None, :], (dmax, W1MAX, 2))
    pred = np.array(pred, dtype=np.float32, copy=True)
    pred[:, 0, 1] = np.minimum(pred[:, 0, 1], avail.astype(np.float32))
    return prices, avail, pred


def _count_rows(kind, arrivals, deadline, n_rows: int, dmax: int):
    """One fleet call's row counters: the rows the program computes (the
    AHAP rule runs over all of them), those that are padding or of
    another kind, and the (row, slot) cells scanned and inside a real
    job's live window ``[arrival, arrival + deadline)``."""
    arr = np.asarray(arrivals, np.int64)
    live = (np.minimum(arr + np.asarray(deadline, np.int64), dmax)
            - np.clip(arr, 0, dmax))
    counters.add("fleet_calls")
    counters.add("fleet_rows", n_rows)
    counters.add("fleet_rows_padded",
                 n_rows - int(np.sum(kind == KIND_AHAP)))
    counters.add("fleet_cells", n_rows * dmax)
    counters.add("fleet_cells_live", int(np.maximum(live, 0).sum()))


def _trim(out, n: int):
    """The result rows of the ``n`` submitted jobs: the leading rows, as
    the layout keeps submission order and puts pads last."""
    if all(v.shape[0] == n for v in out.values()):
        return out
    counters.add("launches", len(out))
    return {k: v[:n] for k, v in out.items()}


def simulate_fleet(pool_rows, jobs: JobArrays, arrivals, tput, prices,
                   avail, pred=None, backend: str = "xla",
                   collect: bool = False, fallback=None):
    """Simulate a fleet of jobs contending for one spot pool, on device.

    ``pool_rows`` — per-job policy rows (``kind``/``omega``/``v``/``sigma``
    /``rho``/``cfrac``, each (J,)), e.g. from
    :func:`policy_rows_from_weights`. ``jobs`` — stacked (J,) JobArrays
    (``fast_sim.stack_jobs``). ``arrivals`` — (J,) absolute arrival slots.
    ``prices``/``avail``/``pred`` — ONE shared market trace ((T,), (T,),
    optional (T, W1MAX, 2) absolute-time forecasts).

    Returns the ``fast_sim._finalize`` dict (utility/value/cost/
    completion_time/z_ddl/completed + (J, T) allocation histories), in
    submission order. Semantics match ``multi_job.MultiJobScheduler`` (the
    numpy oracle): completion times are on each job's local clock. One
    compiled program per (J, T, ``tput``, ``backend``, ``collect``,
    ``fallback``), whatever the policy mix.
    """
    with TraceAnnotation("sched.fleet.prep"):
        rows, n = _norm_rows(pool_rows)
        assert n == int(np.shape(jobs.workload)[0]) \
            == int(np.shape(arrivals)[0])
        prices, avail_np, pred = _prepare_market(prices, avail, pred)
        arr = np.asarray(arrivals, np.int32)
        _count_rows(rows["kind"], arr, counters.to_host(jobs.deadline), n,
                    prices.shape[0])
    with TraceAnnotation("sched.fleet.scan"):
        out = counters.launch(_fleet_call, rows, jobs, arr, tput, prices,
                              avail_np, pred, backend, collect, fallback)
    with TraceAnnotation("sched.fleet.gather"):
        return _trim(out, n)


def simulate_fleet_sharded(pool_rows, jobs: JobArrays, arrivals, tput,
                           prices, avail, pred=None, backend: str = "xla",
                           mesh=None, collect: bool = False, fallback=None):
    """:func:`simulate_fleet` with the job axis laid over the pool mesh.

    Default mesh: ``launch.mesh.make_pool_mesh()`` (1-D over every visible
    device). On a 2-D ``("jobs", "lanes")`` mesh only the ``"jobs"`` axis
    shards (the fleet has no lane axis; lanes replicate), so a lanes-only
    ``(1, n)`` mesh — like a single device — falls through to the
    unsharded scan. The rows pad to device divisibility with ``arrival =
    T`` sentinel jobs (never live, zero demand: provably inert in the
    waterfall), one program per (J, T, static flags) whatever the mix, and
    results are bitwise-equal to :func:`simulate_fleet` (pinned in
    tests/test_fleet.py)."""
    from repro.launch.mesh import make_pool_mesh, pool_mesh_job_axes

    mesh = make_pool_mesh() if mesh is None else mesh
    _, n_jobs_dev, _ = pool_mesh_job_axes(mesh)
    if n_jobs_dev <= 1:
        return simulate_fleet(pool_rows, jobs, arrivals, tput, prices,
                              avail, pred, backend, collect, fallback)

    with TraceAnnotation("sched.fleet.prep"):
        rows, n = _norm_rows(pool_rows)
        assert n == int(np.shape(jobs.workload)[0]) \
            == int(np.shape(arrivals)[0])
        prices, avail_np, pred = _prepare_market(prices, avail, pred)
        dmax = prices.shape[0]
        n_rows = n_jobs_dev * -(-n // n_jobs_dev)
        pad = n_rows - n
        # pads repeat the first job (finite inputs) and never arrive
        arr = np.concatenate([np.asarray(arrivals, np.int32),
                              np.full(pad, dmax, np.int32)])
        gidx = np.concatenate([np.arange(n), np.zeros(pad, np.int64)])
        rows = {k: v[gidx] for k, v in rows.items()}
        jobs = JobArrays(*[counters.to_host(f)[gidx] for f in jobs])
        _count_rows(rows["kind"][:n], arr[:n], jobs.deadline[:n], n_rows,
                    dmax)
    with TraceAnnotation("sched.fleet.scan"):
        call = _sharded_fleet_call(mesh, tput, backend, collect, fallback)
        out = counters.launch(call, rows, jobs, arr, prices, avail_np, pred)
    with TraceAnnotation("sched.fleet.gather"):
        return _trim(out, n)


# ---------------------------------------------------------------------------
# EG-weighted admission (select -> admit loop)
# ---------------------------------------------------------------------------

def policy_rows_from_weights(pool_arrays, weights, n, rng=None,
                             greedy: bool = False):
    """Per-job policy rows drawn from EG selector weights.

    Algorithm 2's Line 6 "select" generalized to fleet admission: each of
    the ``n`` arriving jobs samples its policy i.i.d. from the selector
    distribution (``greedy=True`` admits everyone on the argmax instead).
    ``pool_arrays`` is the ``specs_to_arrays`` dict the weights were
    learned over. Returns ``(rows, idx)`` — the per-job row dict
    :func:`simulate_fleet` consumes, plus the (n,) pool indices (handy for
    building python oracle policies via ``pool[i].build()``)."""
    from repro.core.selector import sample_policies

    w = np.asarray(weights, np.float64)
    if greedy:
        idx = np.full(int(n), int(np.argmax(w)), np.int64)
    else:
        rng = np.random.default_rng(0) if rng is None else rng
        idx = sample_policies(w, int(n), rng)
    rows = {k: np.asarray(pool_arrays[k])[idx]
            for k in _POLICY_KEYS if k in pool_arrays}
    return rows, idx.astype(np.int32)
