"""The fleet entry: the multi-job extension (Sec. III-A) through
``fleet.simulate_fleet_sharded``, its job axis laid over the cell's chips.

Each call is one fleet episode: a burst of jobs arrives over a few slots,
each admitted on a lane drawn from uniform selector weights, and all of
them contend for the configuration's one spot pool over the call's
horizon. The check replays every job of each checked call through the
plain reference fleet (``bench/ref/fleet.py``: the same rules, a
least-slack waterfall in a plain loop) and compares each job's utility
and each (job, slot) spot grant, and the program's grants against the
supply; the control is that reference one precision lower in the
program's place. The jobs of a call are coupled through the pool, so the
check compares whole calls.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.entries.select import OFF_TOL, precision, program_pool, tput_of
from bench.gen.fleet import Traffic
from bench.gen.traffic import lane_arrays
from bench.ref import fleet as ref_fleet
from bench.ref import forecast as ref_forecast

__all__ = ["Traffic", "Driver", "check", "control_window"]


# ---------------------------------------------------------------------------
# The program under test
# ---------------------------------------------------------------------------

class Driver:
    """One call: the program's forecast prep for the call's market window,
    the admitted jobs' policy rows, then ``fleet.simulate_fleet_sharded``
    on a job mesh over the cell's chips, waited for. Nothing is carried
    from call to call. Set-up refuses a program that compiles per policy
    mix (:meth:`one_program`)."""

    def __init__(self, cell, traffic: Traffic, devices, spans):
        import jax

        from repro.configs.base import ThroughputConfig
        from repro.core import engine, fleet, market
        from repro.core.fast_sim import JobArrays
        from repro.launch.mesh import make_pool_mesh

        self.jax, self.engine, self.fleet = jax, engine, fleet
        self.JobArrays = JobArrays
        self.tr, self.spans = traffic, spans
        pool = program_pool(traffic.lanes)
        self.pool = {k: np.asarray(pool[k]) for k in fleet._POLICY_KEYS}
        self.tput = ThroughputConfig(**tput_of(cell.config))
        self.trace = market.Trace(traffic.prices, traffic.avail)
        self.mesh = make_pool_mesh(devices)
        self.noise = cell.config["noise"]
        self.one_program(traffic)

    def one_program(self, traffic: Traffic) -> None:
        """Refuse, at set-up, a program that compiles again when the
        admitted policy mix changes: every call of the cell draws a new
        mix, and nothing may compile in the measured window. Two calls on
        one draw whose AHAP count differs by one job; the second must
        compile nothing (a compile served from the persistent cache counts
        too)."""
        from bench.harness import CompileCounter

        ahap = [i for i, l in enumerate(traffic.lanes) if l["kind"] == "ahap"]
        other = [i for i, l in enumerate(traffic.lanes)
                 if l["kind"] != "ahap"]
        if not ahap or not other:
            return
        inp = traffic.warmup(0)
        flip = dict(inp, lanes=inp["lanes"].copy())
        flip["lanes"][0] = other[0] if flip["lanes"][0] in ahap else ahap[0]
        self.call(inp)
        counter = CompileCounter()
        self.call(flip)
        if counter.n:
            raise SystemExit(
                f"bench: the fleet program compiled {counter.n} more "
                "program(s) for a policy mix one AHAP job apart; this cell "
                "draws a new mix every call, so it cannot run without "
                "compiling in the measured window")

    def reset(self):
        pass

    def call(self, inp: dict):
        span = self.spans.span
        h = self.tr.horizon
        with span("bench.call"):
            with span("bench.prep"):
                n = self.noise
                prices, avail, preds = self.engine.prepare_noisy_inputs(
                    self.trace, np.array([inp["t0"]]), h, n["kind"],
                    n["level"], np.array([inp["noise_seed"]]),
                    horizon=n["horizon"], avail_max=n["avail_max"])
                rows = {k: v[inp["lanes"]] for k, v in self.pool.items()}
            with span("bench.engine"):
                out = self.fleet.simulate_fleet_sharded(
                    rows, self.JobArrays(**inp["jobs"]), inp["arrivals"],
                    self.tput, prices[0], avail[0], preds[0], mesh=self.mesh)
            with span("bench.fetch"):
                self.jax.block_until_ready((out["utility"], out["n_spot"]))
        return out

    def keep(self, out):
        """What the check needs of a call: each job's utility and its spot
        grants (moved to the host by :meth:`settle`)."""
        return {"u": out["utility"], "spot": out["n_spot"]}

    def settle(self, kept: List[dict], need) -> None:
        """Move what the calls in ``need`` kept to the host and drop every
        device array."""
        for i, k in enumerate(kept):
            for key in ("u", "spot"):
                k[key] = np.asarray(k[key]) if i in need else None


# ---------------------------------------------------------------------------
# The check against the reference
# ---------------------------------------------------------------------------

def ref_call(traffic: Traffic, inp: dict, ft):
    """The reference fleet on a call's inputs, its own forecast and market
    window: (utilities (J,), spot grants (J, H), supply (H,))."""
    n = traffic.config["noise"]
    h = traffic.horizon
    t0 = inp["t0"]
    p = traffic.prices[t0:t0 + h + 1]
    a = traffic.avail[t0:t0 + h + 1]
    fc = ref_forecast.forecast(p, a, n["kind"], n["level"],
                               inp["noise_seed"], n["horizon"],
                               n["avail_max"])
    lanes = {k: v[inp["lanes"]]
             for k, v in lane_arrays(traffic.lanes).items()}
    u, spot = ref_fleet.simulate(
        inp["jobs"], lanes, inp["arrivals"], tput_of(traffic.config),
        p[:h], a[:h], fc[:h], ft=ft)
    return u, spot, a[:h]


def check(traffic: Traffic, kept: List[dict], samples) -> Dict[str, float]:
    """Compared numbers of a fleet cell's window, over every checked call
    (the rows each sample names; a fleet cell's traffic names them all):
    the share of jobs whose utility is more than ``OFF_TOL`` from the
    reference's (``util_off_share``), the share of (job, slot) spot grants
    of the horizon that differ from the reference's (``spot_off_share``),
    and the most any slot's granted spot exceeds its supply
    (``over_grant``; 0 when every slot's grants fit)."""
    ft = precision(traffic.config)
    gaps, spot_off, over = [], [], 0
    for c, rows in samples:
        u_ref, s_ref, supply = ref_call(traffic, traffic.call(c), ft)
        u = np.asarray(kept[c]["u"], np.float64)
        spot = np.asarray(kept[c]["spot"])
        gaps.append(np.abs(u[rows] - u_ref[rows]))
        spot_off.append(spot[rows] != s_ref[rows])
        over = max(over, int(np.max(spot.sum(axis=0) - supply)))
    g = np.concatenate(gaps)
    g = np.where(np.isfinite(g), g, np.inf)
    return {"util_gap_max": float(g.max()),
            "util_off_share": float(np.mean(g > OFF_TOL)),
            "spot_off_share": float(np.mean(np.concatenate(spot_off))),
            "over_grant": float(max(over, 0))}


# ---------------------------------------------------------------------------
# The control: the reference one precision lower in the program's place
# ---------------------------------------------------------------------------

def control_window(traffic: Traffic, n_calls: int, low) -> list:
    """What a window of ``n_calls`` calls keeps for the check (see
    ``Driver.keep``), with the reference fleet in precision ``low`` as the
    program."""
    kept = []
    for c in range(n_calls):
        u, spot, _ = ref_call(traffic, traffic.call(c), low)
        kept.append({"u": u, "spot": spot})
    return kept
