"""Plain reference of the multi-job extension (arXiv:2512.20967, Sec.
III-A): jobs arrive over time and contend for one spot pool.

Slot by slot over one shared market window: every live job (arrived,
before its deadline, not finished) decides with its own lane's rule
against the slot's full supply, its forecast's present availability capped
by that supply; spot demand is clipped to the supply and the job's
N^max, and on-demand to what N^max leaves. The supply is then granted
least-slack-first in a plain loop, each job taking what it asked for of
what is left; slack is the slots to spare if the job ran at N^max from
now on, in the precision ``ft``, ties broken by job id. Each job then
executes its on-demand and its grant (a grant under N^min is topped up
with on-demand). On-demand is unlimited.

Built on the single-job reference ``bench/ref/sched.py`` and independent
of the program: it imports nothing from it.
"""
from __future__ import annotations

import numpy as np

from bench.ref.sched import (
    AHAP,
    AhapState,
    Exec,
    Jobs,
    _cast_lane,
    _f,
    ahap_decide,
    cheap_decide,
)


def _sub(jb: Jobs, idx) -> Jobs:
    return Jobs({k: np.asarray(v)[idx] for k, v in jb.fields.items()}, jb.ft)


def simulate(jobs: dict, lanes: dict, arrivals, tput: dict, prices, supply,
             pred, ft=np.float32):
    """One fleet episode. ``jobs`` fields (J,), ``lanes`` each job's lane
    arrays (J,) (``kind``, ``omega``, ``v``, ``sigma``, ``cfrac``),
    ``arrivals`` (J,) slots, and the shared market: ``prices``/``supply``
    (H,) and the forecast ``pred`` (H, W1, 2) made at each slot. Returns
    (utilities (J,) float64, spot grants (J, H))."""
    jb = Jobs(jobs, ft)
    arrivals = np.asarray(arrivals, np.int64)
    n, h = len(arrivals), len(prices)
    ex = Exec(n, ft)
    kind = np.asarray(lanes["kind"])
    groups = []
    for is_ahap in (True, False):
        idx = np.flatnonzero((kind == AHAP) == is_ahap)
        groups.append((is_ahap, idx, _sub(jb, idx), _cast_lane(lanes, idx, ft),
                       AhapState(len(idx), ft)))
    h_max = _f(ft, tput["alpha"]) * jb.nmax.astype(ft) + _f(ft, tput["beta"])
    spot = np.zeros((n, h), np.int32)
    for t in range(h):
        price = _f(ft, prices[t])
        s = int(supply[t])
        lt = t - arrivals
        live = (lt >= 0) & (lt < jb.dl) & ~ex.done
        row = np.array(pred[t], ft, copy=True)
        row[0, 1] = min(row[0, 1], _f(ft, s))
        prev_s = int(supply[t - 1]) if t >= 1 else s

        # every live job demands against the full supply
        d_o = np.zeros(n, np.int32)
        d_s = np.zeros(n, np.int32)
        for is_ahap, idx, gjb, glane, gst in groups:
            on = live[idx]
            if not on.any():
                continue
            sel = idx[on]
            sjb = _sub(gjb, on)
            slane = {k: v[on] for k, v in glane.items()}
            lt_s = lt[sel].astype(np.int32)
            s_s = np.full(len(sel), s, np.int32)
            if is_ahap:
                st = AhapState(0, ft)
                st.plans = gst.plans[on]
                n_o, n_s = ahap_decide(
                    sjb, tput, slane, st, ex.z[sel], lt_s, price, s_s,
                    np.broadcast_to(row, (len(sel),) + row.shape))
                gst.plans[on] = st.plans
            else:
                prev = np.where(lt_s >= 1, prev_s, s).astype(np.int32)
                n_o, n_s = cheap_decide(sjb, tput, slane, ex.z[sel], lt_s,
                                        price, s_s, ex.n_prev[sel], prev)
            d_o[sel], d_s[sel] = n_o, n_s
        d_s = np.clip(d_s, 0, np.minimum(s, jb.nmax))
        d_o = np.clip(d_o, 0, jb.nmax - d_s)
        d_s = np.where(live, d_s, 0)
        d_o = np.where(live, d_o, 0)

        # least slack first, each job taking what it asked of what is left
        slack = ((arrivals + jb.dl - t).astype(ft)
                 - np.maximum(jb.wl - ex.z, _f(ft, 0.0)) / h_max)
        grant = np.zeros(n, np.int32)
        left = s
        for j in sorted(np.flatnonzero(live),
                        key=lambda j: (float(slack[j]), int(j))):
            grant[j] = min(int(d_s[j]), left)
            left -= grant[j]

        # execute on each job's own clock; before arrival it is inactive
        t_loc = np.where(lt >= 0, lt, jb.dl)
        _, spot[:, t], _ = ex.step(jb, tput, t_loc, d_o, grant, price, grant)
    return ex.utility(jb, tput).astype(np.float64), spot
