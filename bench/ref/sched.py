"""Plain reference of the scheduler's semantics, in numpy.

Written from the paper (Sec. III execution model, Alg. 1 AHAP with its
CHC window problem, Alg. 3 AHANP, the Sec. VI-A baselines) and the
RAND_DEADLINE rule of arXiv:2601.14612, and independent of the program: it
imports nothing from it. Every floating-point quantity is held in the dtype
``ft`` given to the entry points (float32 by default, the precision the
configurations state), with the arithmetic done in the order the
equations are written. Arrays run over a flat batch of cells, one cell per
(job, lane), and over slots in a plain loop.

Entry points:

``simulate_cells``  utilities of independent (job, lane) cells.
``normalize``       per-job normalisation of a (K, M) utility matrix.
``eg_run``          the EG update over its rows (Alg. 2).
"""
from __future__ import annotations

import numpy as np

from bench.gen.traffic import KINDS

AHAP, AHANP, OD, MSU, UP, RAND = range(6)
assert KINDS[AHAP] == "ahap" and KINDS[RAND] == "rand_deadline"

W1 = 6          # forecast rows a decision sees: the present + 5 slots
VMAX = 5        # most plans AHAP averages
TABLE_N = 16    # most instances a slot can hold (the availability cap)
BIG = 1.0e9     # cost of an infeasible purchase
TIE_EPS = 2.0 ** -10   # per-unit bias: of two equal objectives, fewer units


class Jobs:
    """Per-cell job fields as arrays of ``ft`` (floats) and int32."""

    def __init__(self, fields: dict, ft):
        c = lambda k: np.asarray(fields[k], ft)
        i = lambda k: np.asarray(fields[k], np.int32)
        self.fields, self.ft = fields, ft
        self.wl, self.val, self.gam, self.po = (
            c("workload"), c("value"), c("gamma"), c("p_o"))
        self.dl, self.nmin, self.nmax = i("deadline"), i("n_min"), i("n_max")

    def column(self) -> "Jobs":
        """The same jobs, each field a (B, 1) column to broadcast against a
        second axis."""
        return Jobs({k: np.asarray(v)[:, None] for k, v in self.fields.items()},
                    self.ft)


def _f(ft, x):
    return np.asarray(x, ft)


# ---------------------------------------------------------------------------
# Value and termination (Eq. 4, Eq. 9)
# ---------------------------------------------------------------------------

def value_fn(jb: Jobs, T):
    """V(T): full value until the deadline, linear decay to 0 at gamma*d."""
    ft = jb.ft
    d = jb.dl.astype(ft)
    decay = jb.val * (_f(ft, 1.0) - (T - d) / ((jb.gam - _f(ft, 1.0)) * d))
    return np.where(T <= d, jb.val, np.clip(decay, _f(ft, 0.0), jb.val))


def tilde_value(jb: Jobs, tput: dict, z):
    """V~(Z): value at completion minus the on-demand cost of finishing the
    rest with N^max instances past the deadline (the termination
    configuration)."""
    ft = jb.ft
    rate = _f(ft, tput["alpha"]) * jb.nmax.astype(ft) + _f(ft, tput["beta"])
    dt = np.maximum(jb.wl - z, _f(ft, 0.0)) / rate
    val = value_fn(jb, jb.dl.astype(ft) + dt)
    return val - jb.po * jb.nmax.astype(ft) * dt


# ---------------------------------------------------------------------------
# Feasibility and execution of one slot (Sec. III, constraints (5b)-(5d))
# ---------------------------------------------------------------------------

def repair(jb: Jobs, n_o, n_s, price, avail):
    """A policy's own repair of its request: spot capped by availability
    and N^max; a total under N^min topped up with the cheaper source; a
    total over N^max trimmed, on-demand first when spot is the cheaper."""
    n_s = np.minimum(np.minimum(n_s, avail), jb.nmax)
    n_o = np.maximum(n_o, 0)
    total = n_o + n_s
    need = np.maximum(jb.nmin - total, 0)
    short = (total > 0) & (total < jb.nmin)
    spot_room = (price <= jb.po) & (avail - n_s >= need)
    n_s = np.where(short & spot_room, n_s + need, n_s)
    n_o = np.where(short & ~spot_room, n_o + need, n_o)
    over = np.maximum(n_o + n_s - jb.nmax, 0)
    drop_od = np.where(price <= jb.po, np.minimum(over, n_o), 0)
    n_o = n_o - drop_od
    n_s = n_s - (over - drop_od)
    zero = total <= 0
    return np.where(zero, 0, n_o), np.where(zero, 0, n_s)


class Exec:
    """Execution state of a batch of cells: progress z, last allocation,
    cost, completion flag and fractional completion time."""

    def __init__(self, n: int, ft):
        self.ft = ft
        self.z = np.zeros(n, ft)
        self.n_prev = np.zeros(n, np.int32)
        self.cost = np.zeros(n, ft)
        self.done = np.zeros(n, bool)
        self.T = np.zeros(n, ft)

    def step(self, jb: Jobs, tput: dict, t, n_o, n_s, price, avail):
        """Run one slot: hard clip to what the market and the job allow,
        the reconfiguration factor mu, whole-slot billing, and completion
        part-way through the slot."""
        ft = self.ft
        active = (t < jb.dl) & ~self.done
        n_s = np.clip(n_s, 0, np.minimum(avail, jb.nmax))
        n_o = np.clip(n_o, 0, jb.nmax - n_s)
        n = n_o + n_s
        n_o = np.where((n > 0) & (n < jb.nmin), n_o + (jb.nmin - n), n_o)
        n_o = np.where(active, n_o, 0)
        n_s = np.where(active, n_s, 0)
        n = n_o + n_s
        mu = np.where(n > self.n_prev, _f(ft, tput["mu1"]),
                      np.where(n < self.n_prev, _f(ft, tput["mu2"]),
                               _f(ft, 1.0)))
        mu = np.where((n == 0) & (self.n_prev == 0), _f(ft, 1.0), mu)
        h = _f(ft, tput["alpha"]) * n.astype(ft) + _f(ft, tput["beta"])
        work = mu * np.where(n > 0, h, _f(ft, 0.0))
        finish = active & (work > 0) & (self.z + work >= jb.wl)
        with np.errstate(divide="ignore", invalid="ignore"):
            frac = np.where(work > 0, (jb.wl - self.z)
                            / np.maximum(work, _f(ft, 1e-9)), _f(ft, 0.0))
        t_f = np.asarray(t).astype(ft)
        self.T = np.where(finish, t_f + frac, self.T)
        bill = n_s.astype(ft) * price + n_o.astype(ft) * jb.po
        self.cost = self.cost + np.where(active, bill, _f(ft, 0.0))
        self.z = np.minimum(self.z + np.where(active, work, _f(ft, 0.0)),
                            jb.wl)
        self.n_prev = np.where(active, n, self.n_prev)
        self.done = self.done | finish
        return n_o, n_s, active

    def utility(self, jb: Jobs, tput: dict):
        """Value minus cost, unfinished work completed past the deadline by
        the termination configuration."""
        ft = self.ft
        h_max = _f(ft, tput["alpha"]) * jb.nmax.astype(ft) + _f(ft, tput["beta"])
        dt = np.maximum(jb.wl - self.z, _f(ft, 0.0)) / h_max
        T = np.where(self.done, self.T, jb.dl.astype(ft) + dt)
        cost = self.cost + np.where(self.done, _f(ft, 0.0),
                                    jb.po * jb.nmax.astype(ft) * dt)
        return value_fn(jb, T) - cost


# ---------------------------------------------------------------------------
# The CHC window problem (Eq. 10), solved exactly by a min-plus DP
# ---------------------------------------------------------------------------

def solve_window(jb: Jobs, tput: dict, z0, horizon, prices, avail):
    """Best purchase over the forecast window for each cell.

    ``prices``/``avail`` are (B, W1) forecasts; slots at or beyond
    ``horizon`` (B,) buy nothing. A slot buys k instances spot first (spot
    only where its forecast price is at most on-demand), so its cheapest
    cost for k is known; a DP over slots gives the cheapest cost of every
    total u of instance-slots, and the best u maximises V~(z0 + alpha u)
    minus that cost. Returns (n_o, n_s) per window slot, (B, W1) each."""
    ft = jb.ft
    b, w1 = prices.shape
    tn = TABLE_N
    U = w1 * tn
    in_h = np.arange(w1)[None, :] < horizon[:, None]                # (B, W1)
    spot_ok = (prices <= jb.po[:, None]) & in_h
    spot_units = np.where(spot_ok, np.minimum(avail, jb.nmax[:, None]), 0)
    ks = np.arange(tn + 1).astype(ft)[None, None, :]               # (1,1,K)
    n_sp = np.minimum(ks, spot_units[..., None].astype(ft))
    cost = n_sp * prices[..., None] + (ks - n_sp) * jb.po[:, None, None]
    feasible = (ks == 0) | ((ks >= jb.nmin[:, None, None].astype(ft))
                            & (ks <= jb.nmax[:, None, None].astype(ft))
                            & in_h[..., None])
    cost = np.where(feasible, cost, _f(ft, BIG))                    # (B,W1,K)

    big = _f(ft, BIG)
    C = np.where(np.arange(U + 1)[None, :] == 0, _f(ft, 0.0), big)
    C = np.broadcast_to(C, (b, U + 1)).copy()
    choice = np.zeros((w1, b, U + 1), np.int32)
    for s in range(w1):
        row = cost[:, s, :]
        padded = np.concatenate([np.full((b, tn), big, ft), C], axis=1)
        best = C + row[:, 0:1]
        bestk = np.zeros((b, U + 1), np.int32)
        for k in range(1, tn + 1):
            cand = padded[:, tn - k: tn - k + U + 1] + row[:, k:k + 1]
            take = cand < best          # the smallest k wins a tie
            best = np.where(take, cand, best)
            bestk = np.where(take, k, bestk)
        C = best
        choice[s] = bestk

    u = np.arange(U + 1).astype(ft)[None, :]
    zs = np.asarray(z0, ft)[:, None] + _f(ft, tput["alpha"]) * u
    gain = tilde_value(jb.column(), tput, zs) - _f(ft, TIE_EPS) * u
    obj = np.where(C < big / _f(ft, 2.0), gain - C, -np.inf)
    u_best = np.argmax(obj, axis=1)          # the smallest u wins a tie
    n_tot = np.zeros((b, w1), np.int32)
    rows = np.arange(b)
    for s in range(w1 - 1, -1, -1):
        k = choice[s][rows, u_best]
        n_tot[:, s] = k
        u_best = u_best - k
    n_s = np.minimum(n_tot, spot_units).astype(np.int32)
    return n_tot - n_s, n_s


# ---------------------------------------------------------------------------
# Decision rules
# ---------------------------------------------------------------------------

class AhapState:
    """AHAP's per-cell memory: the plans committed at the last VMAX slots
    (newest first), each (W1, 2) of (n_o, n_s)."""

    def __init__(self, n: int, ft):
        self.plans = np.zeros((n, VMAX, W1, 2), ft)


def ahap_decide(jb: Jobs, tput: dict, lane, st: AhapState, z, t, price,
                avail, pred):
    """Alg. 1 for a batch of AHAP cells at local slot ``t`` ((B,) or
    scalar). ``pred`` is each cell's (B, W1, 2) forecast row; ``lane``
    holds (B,) ``omega``, ``v``, ``sigma``. Ahead of the uniform schedule
    through the window: take every forecast slot whose spot price is under
    sigma p^o, all the spot it offers. Behind: solve the CHC window problem.
    The decision averages the last v plans' entries for this slot."""
    ft = jb.ft
    b = z.shape[0]
    omega, v, sigma = lane["omega"], lane["v"], lane["sigma"]
    t = np.broadcast_to(np.asarray(t), (b,))
    in_w = np.arange(W1)[None, :] <= omega[:, None]
    p_f, a_f = pred[..., 0], pred[..., 1]
    end = np.minimum((t + 1 + omega).astype(ft), jb.dl.astype(ft))
    z_exp_end = jb.wl / jb.dl.astype(ft) * end
    thr_s = np.where(in_w & (p_f <= (sigma * jb.po)[:, None])
                     & (a_f >= jb.nmin[:, None].astype(ft)),
                     np.minimum(a_f.astype(np.int32), jb.nmax[:, None]), 0)
    horizon = np.minimum(jb.dl - t, omega + 1)
    chc_o, chc_s = solve_window(jb, tput, z, horizon, p_f,
                                a_f.astype(np.int32))
    ahead = (z >= z_exp_end)[:, None]
    plan = np.stack([np.where(ahead, 0, chc_o),
                     np.where(ahead, thr_s, chc_s)], axis=-1).astype(ft)
    st.plans = np.concatenate([plan[:, None], st.plans[:, :-1]], axis=1)
    kk = np.arange(VMAX)
    valid = (kk[None, :] < v[:, None]) & (kk[None, :] <= t[:, None])
    # the plan made kk slots ago holds this slot at its entry kk
    diag = st.plans[:, kk, np.minimum(kk, W1 - 1)]               # (B, V, 2)
    cnt = np.maximum(valid.sum(axis=1), 1).astype(ft)
    tot = np.zeros((b, 2), ft)
    for k in range(VMAX):
        tot = tot + diag[:, k] * valid[:, k, None].astype(ft)
    avg = tot / cnt[:, None]
    n_o = np.floor(avg[:, 0] + _f(ft, 0.5)).astype(np.int32)
    n_s = np.minimum(np.floor(avg[:, 1] + _f(ft, 0.5)).astype(np.int32),
                     avail)
    zero = (n_o + n_s) == 0
    n_o, n_s = repair(jb, n_o, n_s, price, avail)
    return np.where(zero, 0, n_o), np.where(zero, 0, n_s)


def cheap_decide(jb: Jobs, tput: dict, lane, z, t, price, avail, n_prev,
                 prev_avail):
    """The prediction-free rules, each cell by its lane's ``kind``:
    AHANP (Alg. 3), OD-Only, MSU, UP and RAND_DEADLINE."""
    ft = jb.ft
    kind, sigma, cfrac = lane["kind"], lane["sigma"], lane["cfrac"]
    b = z.shape[0]
    t = np.broadcast_to(np.asarray(t), (b,))
    t_f = t.astype(ft)
    one, zero_f = _f(ft, 1.0), _f(ft, 0.0)
    remaining = np.maximum(jb.wl - z, zero_f)
    slots_left = (jb.dl - t).astype(ft)
    alpha = _f(ft, tput["alpha"])
    od_need = np.ceil(remaining / np.maximum(slots_left, one)
                      / alpha).astype(np.int32)

    # AHANP: progress, price and availability indicators
    with np.errstate(divide="ignore", invalid="ignore"):
        z_exp = jb.wl / jb.dl.astype(ft) * t_f
        z_hat = np.where(z_exp > 0, z / z_exp, one)
        p_hat = price / (sigma * jb.po)
        n_hat = np.where(avail == 0, zero_f,
                         np.where(prev_avail == 0, _f(ft, np.inf),
                                  avail.astype(ft)
                                  / np.maximum(prev_avail, 1).astype(ft)))
    grow = np.where(p_hat > 1.0, n_prev, np.maximum(n_prev, avail))
    hold = np.where(n_hat <= 1.0, n_prev, grow)
    ahead_n = np.where(avail == 0, 0,
                       np.where(n_hat <= 0.5,
                                np.maximum(n_prev // 2, jb.nmin), hold))
    n = np.where(z_hat >= 1.0, ahead_n, np.maximum(2 * n_prev, jb.nmin))
    n_c = np.clip(n, jb.nmin, jb.nmax)
    an_s = np.minimum(avail, n_c)
    an = repair(jb, n_c - an_s, an_s, price, avail)
    an = tuple(np.where(n <= 0, 0, x) for x in an)

    # OD-Only: on-demand sized to finish exactly at the deadline
    od = repair(jb, np.clip(od_need, jb.nmin, jb.nmax), 0, price, avail)
    od_off = (remaining <= 0) | (slots_left <= 0)
    od = tuple(np.where(od_off, 0, x) for x in od)

    # MSU: all spot; on-demand once N^max over the slots left but one
    # would no longer finish
    ms_s = np.minimum(avail, jb.nmax)
    h_max = alpha * jb.nmax.astype(ft) + _f(ft, tput["beta"])
    panic = remaining > h_max * np.maximum(slots_left - one, zero_f)
    ms_o = np.where(panic,
                    np.maximum(np.minimum(od_need, jb.nmax) - ms_s, 0), 0)
    ms_off = (remaining <= 0) | ((ms_s + ms_o) == 0)
    ms = tuple(np.where(ms_off, 0, x)
               for x in repair(jb, ms_o, ms_s, price, avail))

    # UP: follow the L/d line, spot first, on-demand only when behind
    rate = jb.wl / jb.dl.astype(ft)
    deficit = np.maximum(rate * t_f - z, zero_f)
    up_need = np.clip(np.ceil((rate + deficit) / alpha).astype(np.int32),
                      jb.nmin, jb.nmax)
    up_s = np.minimum(avail, up_need)
    up_o = np.where(deficit > 0, up_need - up_s, 0)
    up_off = (remaining <= 0) | ((up_s + up_o) == 0)
    up = tuple(np.where(up_off, 0, x)
               for x in repair(jb, up_o, up_s, price, avail))

    # RAND_DEADLINE: all spot before slot floor(f d), then OD-Only sizing
    tau = np.floor(cfrac * jb.dl.astype(ft))
    committed = t_f >= tau
    rd_o = np.where(committed, np.clip(od_need, jb.nmin, jb.nmax), 0)
    rd_s = np.where(committed, 0, np.minimum(avail, jb.nmax))
    rd_off = (remaining <= 0) | (slots_left <= 0) | ((rd_o + rd_s) == 0)
    rd = tuple(np.where(rd_off, 0, x)
               for x in repair(jb, rd_o, rd_s, price, avail))

    pick = lambda i: np.select(
        [kind == AHANP, kind == OD, kind == MSU, kind == UP, kind == RAND],
        [an[i], od[i], ms[i], up[i], rd[i]], 0)
    return pick(0), pick(1)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _cast_lane(lane: dict, idx, ft):
    return {"kind": lane["kind"][idx], "omega": lane["omega"][idx],
            "v": lane["v"][idx], "sigma": np.asarray(lane["sigma"][idx], ft),
            "cfrac": np.asarray(lane["cfrac"][idx], ft)}


def simulate_cells(jobs: dict, lanes: dict, tput: dict, prices, avail, pred,
                   ft=np.float32):
    """Utility of every (job, lane) cell: ``jobs`` fields (S,), ``lanes``
    arrays (M,), market windows ``prices``/``avail`` (S, d) and forecasts
    ``pred`` (S, d, W1, 2). Returns (S, M) utilities as float64."""
    s_n = len(np.asarray(jobs["workload"]))
    m_n = len(lanes["kind"])
    d = np.asarray(prices).shape[1]
    job_of = np.repeat(np.arange(s_n), m_n)
    lane_of = np.tile(np.arange(m_n), s_n)
    out = np.zeros(s_n * m_n)
    for is_ahap in (True, False):
        sel = (lanes["kind"][lane_of] == AHAP) == is_ahap
        if not sel.any():
            continue
        jx, lx = job_of[sel], lane_of[sel]
        jb = Jobs({k: np.asarray(v)[jx] for k, v in jobs.items()}, ft)
        ln = _cast_lane(lanes, lx, ft)
        ex = Exec(len(jx), ft)
        pr = np.asarray(prices, ft)[jx]
        av = np.asarray(avail).astype(np.int32)[jx]
        pm = np.asarray(pred, ft)[jx]
        ah = AhapState(len(jx), ft)
        prev_av = av[:, 0]
        for t in range(d):
            if is_ahap:
                n_o, n_s = ahap_decide(jb, tput, ln, ah, ex.z, t, pr[:, t],
                                       av[:, t], pm[:, t])
            else:
                n_o, n_s = cheap_decide(jb, tput, ln, ex.z, t, pr[:, t],
                                        av[:, t], ex.n_prev, prev_av)
            _, _, active = ex.step(jb, tput, t, n_o, n_s, pr[:, t], av[:, t])
            prev_av = np.where(active, av[:, t], prev_av)
        out[sel] = ex.utility(jb, tput).astype(np.float64)
    return out.reshape(s_n, m_n)


def normalize(jobs: dict, u):
    """Per-job [0, 1] normalisation for Thm. 2: u_max = v, u_min = the
    worst spend with zero value, N^max on-demand for gamma d slots."""
    v = np.asarray(jobs["value"], float)
    lo = -(np.asarray(jobs["p_o"], float) * np.asarray(jobs["n_max"], float)
           * np.asarray(jobs["gamma"], float)
           * np.asarray(jobs["deadline"], float))
    return np.clip((np.asarray(u, float) - lo[:, None])
                   / (v - lo)[:, None], 0.0, 1.0)


def eg_run(cum_utils, cum_expected: float, eta: float, un, ft=np.float64):
    """The EG update (Alg. 2, Lines 7-11) over the rows of ``un``, from a
    selector whose per-policy cumulative utilities are ``cum_utils``: the
    weights are softmax(eta * cumulative utility), the multiplicative update
    from uniform weights. Returns (weights, cum_utils, cum_expected,
    regret) after the last row."""
    cu = np.asarray(cum_utils, ft).copy()
    ce = ft(cum_expected)
    eta = ft(eta)
    w = _softmax(eta * (cu - cu.max()))
    for u in np.asarray(un, ft):
        ce = ce + ft(np.sum(w * u))
        cu = cu + u
        w = _softmax(eta * (cu - cu.max()))
    return w, cu, ce, ft(cu.max() - ce)


def _softmax(x):
    e = np.exp(x)
    return e / e.sum()
