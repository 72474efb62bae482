"""The selection entry: online policy selection (Alg. 2) through
``engine.simulate_and_select``, its job axis laid over the cell's chips.

Each call is a batch of jobs, each simulated under every lane of the
configuration's pool over its deadline, then normalised into the EG
selector, whose state is carried from call to call. The check compares
every lane's utility for sampled jobs against the plain reference
simulator (``bench/ref/sched.py``) and each sampled call's EG step; the
control is that reference one precision lower in the program's place.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.gen.traffic import Traffic, lane_arrays
from bench.ref import forecast as ref_forecast
from bench.ref import sched as ref

__all__ = ["Traffic", "Driver", "check", "control_window"]

# a (job, lane) utility further than this from the reference's is "off":
# the repo's own python-vs-device tolerance, in the value's units (v = 120)
OFF_TOL = 1e-2
BLOCK = 250     # job rows the control's reference simulates at once


def tput_of(config: dict) -> dict:
    return {k: float(v) for k, v in config["throughput"].items()}


def precision(config: dict):
    """The float type the configuration states, which the reference uses."""
    return np.dtype(config["precision"]).type


def eg_eta(config: dict, lanes: int) -> float:
    """The EG learning rate: Thm. 2's for the configuration's horizon."""
    return float(np.sqrt(2.0 * np.log(lanes) / int(config["eg_horizon_jobs"])))


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

def program_pool(lanes: list) -> dict:
    """The program's lane encoding of a config's lanes, in the same order."""
    from repro.core.policy_pool import KIND_NAMES, PolicySpec, specs_to_arrays

    code = {name: k for k, name in KIND_NAMES.items()}
    return specs_to_arrays([
        PolicySpec(code[l["kind"]], l["omega"], l["v"], l["sigma"])
        for l in lanes])


class Driver:
    """One call: the program's forecast prep for the call's jobs, then
    ``engine.simulate_and_select`` with its default options on a job mesh
    over the cell's chips, the EG state carried from the previous call.
    Utilities come back with the result so that the check can compare
    them."""

    def __init__(self, cell, traffic: Traffic, devices, spans):
        from repro.configs.base import ThroughputConfig
        from repro.core import engine, market, selector
        from repro.core.fast_sim import JobArrays
        from repro.launch.mesh import make_pool_mesh

        self.engine, self.JobArrays = engine, JobArrays
        self.tr, self.spans = traffic, spans
        self.pool = program_pool(traffic.lanes)
        self.tput = ThroughputConfig(**tput_of(cell.config))
        self.trace = market.Trace(traffic.prices, traffic.avail)
        self.mesh = make_pool_mesh(devices)
        self.noise = cell.config["noise"]
        self.state0 = selector.eg_init(len(traffic.lanes),
                                       int(cell.config["eg_horizon_jobs"]))
        self.state = self.state0

    def reset(self):
        self.state = self.state0

    def call(self, inp: dict):
        span = self.spans.span
        with span("bench.call"):
            with span("bench.prep"):
                n = self.noise
                prices, avail, preds = self.engine.prepare_noisy_inputs(
                    self.trace, inp["t0s"], self.tr.deadline, n["kind"],
                    n["level"], inp["noise_seeds"], horizon=n["horizon"],
                    avail_max=n["avail_max"])
            with span("bench.engine"):
                jobs = self.JobArrays(**inp["jobs"])
                res = self.engine.simulate_and_select(
                    self.pool, jobs, self.tput, prices, avail, preds,
                    mesh=self.mesh, state=self.state, return_utilities=True)
            with span("bench.fetch"):
                res.state.weights.block_until_ready()
        self.state = res.state
        return res

    # -- check ------------------------------------------------------------

    @staticmethod
    def host_state(s) -> dict:
        """The selector state as float64: the Kahan-compensated sums and
        the weights."""
        f = lambda x: np.asarray(x, np.float64)
        return {"cu": f(s.cum_utils) - f(s.cum_utils_c),
                "ce": float(f(s.cum_expected) - f(s.cum_expected_c)),
                "w": f(s.weights)}

    def keep(self, res):
        """What the check needs of a call: its utilities and regret (host
        arrays already) and its EG state (moved to the host by
        :meth:`settle` once the window has closed)."""
        return {"u": res.utilities, "state": res.state,
                "regret": float(res.regret[-1])}

    def settle(self, kept: List[dict], need) -> None:
        """Move the kept states of the calls in ``need`` to the host and
        drop every device array."""
        for i, k in enumerate(kept):
            k["state"] = self.host_state(k["state"]) if i in need else None


# ---------------------------------------------------------------------------
# The check against the reference
# ---------------------------------------------------------------------------

def ref_inputs(traffic: Traffic, inp: dict, rows):
    """The reference's own market windows and forecasts for ``rows`` of a
    call, over a job's deadline of d slots: (prices (S, d), avail (S, d),
    pred (S, d, W1, 2))."""
    n = traffic.config["noise"]
    slots = traffic.deadline
    ps, avs, pms = [], [], []
    for r in rows:
        t0 = int(inp["t0s"][r])
        p = traffic.prices[t0:t0 + slots + 1]
        a = traffic.avail[t0:t0 + slots + 1]
        fc = ref_forecast.forecast(p, a, n["kind"], n["level"],
                                   int(inp["noise_seeds"][r]), n["horizon"],
                                   n["avail_max"])
        ps.append(p[:slots])
        avs.append(a[:slots])
        pms.append(fc[:slots])
    return np.stack(ps), np.stack(avs), np.stack(pms)


def _util_numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate([np.ravel(x) for x in gaps])
    g = np.where(np.isfinite(g), g, np.inf)
    return {"util_gap_max": float(g.max()),
            "util_off_share": float(np.mean(g > OFF_TOL))}


def check(traffic: Traffic, kept: List[dict], samples) -> Dict[str, float]:
    """Compared numbers of a selection cell's window. Sampled rows: every lane's
    utility against the reference simulator. Each sampled call's EG step:
    the reference's normalisation and EG update over the call's utility
    matrix (its sampled rows replaced by the reference's own), from the
    selector state the call started with, against the program's weights and
    regret after the call. ``eg_sum_gap`` is the widest gap of a policy's
    cumulative normalised utility, the selector's whole state. The first
    call starts from no utility at all."""
    cfg = traffic.config
    ft = precision(cfg)
    lanes = lane_arrays(traffic.lanes)
    tput = tput_of(cfg)
    m = len(traffic.lanes)
    eta = eg_eta(cfg, m)
    initial = {"cu": np.zeros(m), "ce": 0.0}
    gaps, w_gap, cu_gap, r_gap = [], 0.0, 0.0, 0.0
    for c, rows in samples:
        inp = traffic.call(c)
        p, a, pm = ref_inputs(traffic, inp, rows)
        jobs_s = {k: v[rows] for k, v in inp["jobs"].items()}
        u_ref = ref.simulate_cells(jobs_s, lanes, tput, p, a, pm, ft=ft)
        u_prog = np.asarray(kept[c]["u"], np.float64)
        gaps.append(np.abs(u_prog[rows] - u_ref))
        u_mix = u_prog.copy()
        u_mix[rows] = u_ref
        s_in = kept[c - 1]["state"] if c > 0 else initial
        w, cu, _, regret = ref.eg_run(s_in["cu"], s_in["ce"], eta,
                                      ref.normalize(inp["jobs"], u_mix))
        s_out = kept[c]["state"]
        w_gap = max(w_gap, float(np.max(np.abs(s_out["w"] - w))))
        cu_gap = max(cu_gap, float(np.max(np.abs(s_out["cu"] - cu))))
        r_gap = max(r_gap, abs(kept[c]["regret"] - float(regret))
                    / max(1.0, abs(float(regret))))
    out = _util_numbers(gaps)
    out.update(eg_sum_gap=cu_gap, eg_weight_gap=w_gap, eg_regret_gap=r_gap)
    return out


# ---------------------------------------------------------------------------
# The control: the reference one precision lower in the program's place
# ---------------------------------------------------------------------------

def control_window(traffic: Traffic, n_calls: int, low) -> list:
    """What a window of ``n_calls`` calls keeps for the check (see
    ``Driver.keep``), with the reference in precision ``low`` as the
    program: every job's utility under every lane, then normalisation and
    the EG update from the state the previous call left."""
    cfg = traffic.config
    lanes = lane_arrays(traffic.lanes)
    tput = tput_of(cfg)
    eta = eg_eta(cfg, len(traffic.lanes))
    cu, ce = np.zeros(len(traffic.lanes), low), 0.0
    kept = []
    for c in range(n_calls):
        inp = traffic.call(c)
        u = []
        for lo in range(0, traffic.jobs_per_call, BLOCK):
            rows = np.arange(lo, min(lo + BLOCK, traffic.jobs_per_call))
            p, a, pm = ref_inputs(traffic, inp, rows)
            jobs = {k: v[rows] for k, v in inp["jobs"].items()}
            u.append(ref.simulate_cells(jobs, lanes, tput, p, a, pm, ft=low))
        u = np.concatenate(u)
        w, cu, ce, regret = ref.eg_run(cu, ce, eta,
                                       ref.normalize(inp["jobs"], u), ft=low)
        f = lambda x: np.asarray(x, np.float64)
        kept.append({"u": u, "regret": float(regret),
                     "state": {"cu": f(cu), "ce": float(ce), "w": f(w)}})
    return kept
