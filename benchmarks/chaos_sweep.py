"""Chaos sweep: fault intensity x fallback threshold over the 124-lane
pool, end to end through ``engine.simulate_and_select``.

The robustness claim this bench measures: when the spot market breaks in
ways the predictor did not see coming (preemption storms + price spikes
while the forecast stack stays stale), the prediction-consuming AHAP
lanes armed with the online fallback monitor (``repro.chaos.
FallbackConfig``) beat the same lanes running pure AHAP on the bad
forecasts — and the EG selector re-converges after the storms instead of
thrashing.

Regime (the *forced* storm regime the regression guard pins): an
abundant, cheap pre-storm market (so the stale forecasts are rosy),
deadline-tight workloads (so storm slots lost to phantom-spot deferral
are unrecoverable), and ``storm_schedule`` faults aligned with a
``pred_stale`` predictor freeze — the market turns, the forecasts don't.

Sweep structure per fault intensity (number of storms; 0 = clean):

  off       timed ``simulate_and_select`` with ``fallback=None``
  on        timed run per ``CHAOS_THRESHOLD`` value (each distinct
            FallbackConfig is a distinct compiled program — sweep few)
  collect   one untimed ``collect=True`` flight-recorder pass at the
            first threshold, pinned bitwise against the timed on-run's
            mean utilities, folded into the pool ledger's ``fallback``
            block (trigger/recovery reconciliation) and the selection
            ledger (top-policy switch trace = selector re-convergence)

Headline derived values (AHAP lanes only — cheap lanes carry no monitor):
``chaos_gain__s<max>`` (fallback-on minus fallback-off mean utility at
max intensity; the RUN_BENCH_REGRESSION guard pins it positive) and the
per-intensity on/off utilities.

Env knobs: CHAOS_JOBS (default 64), CHAOS_INTENSITY (comma-separated
storm counts, default "0,1,2"), CHAOS_THRESHOLD (comma-separated EWMA
thresholds, default "0.5"), CHAOS_STORM_LEN, CHAOS_SPIKE, CHAOS_LAM
(monitor EWMA weight), CHAOS_REPEAT,
CHAOS_LEDGER (path: write the collect-pass ledgers as a standalone JSON
artifact — the CI upload); POOL_SIM_MESH / POOL_SIM_JSON as everywhere.
"""
from __future__ import annotations

import json
import os
from typing import List, Tuple

import numpy as np

from benchmarks.common import (PAPER_TPUT, Row, job_stream_arrays,
                               merge_bench_rows, paper_market, timed)
from benchmarks.pool_sim_bench import _JSON_PATH

N_JOBS = int(os.environ.get("CHAOS_JOBS", "64"))
REPEAT = int(os.environ.get("CHAOS_REPEAT", "1"))
INTENSITY = tuple(int(x) for x in
                  os.environ.get("CHAOS_INTENSITY", "0,1,2").split(",") if x)
THRESHOLDS = tuple(float(x) for x in
                   os.environ.get("CHAOS_THRESHOLD", "0.5").split(",") if x)
STORM_LEN = int(os.environ.get("CHAOS_STORM_LEN", "4"))
SPIKE_MAG = float(os.environ.get("CHAOS_SPIKE", "2.5"))
LAM = float(os.environ.get("CHAOS_LAM", "0.5"))
LEDGER_JSON = os.environ.get("CHAOS_LEDGER", "")

# the forced storm regime: rosy pre-storm market + tight workloads (see
# module docstring); deadline matches the paper setting, workloads are
# scaled so the deadline has no slack to absorb a storm
MARKET_SEED = 11
JOB_SEED = 3
# seed 11 lands the single-storm case early in the window, so the monitor
# has clean slots to stand down in — the recovery telemetry is visible
FAULT_SEED = 11
DEADLINE = 10
WORKLOAD_SCALE = 1.4
NOISE_KIND = "magdep_uniform"
NOISE_LEVEL = 0.1
MARKET_KW = dict(avail_mean=9.0, mean_price=0.4, price_sigma=0.3)
PRED_FAULT = "stale"


def build_inputs(n_storms: int, n_jobs: int = N_JOBS):
    """Engine inputs for one fault intensity: the clean per-job windows
    (shared across intensities — paired comparison), faulted by one
    ``storm_schedule`` applied at window-relative slots, so every job
    rides through the same storms. Returns ``(jobs, prices, avail, preds,
    schedule)``."""
    from repro.chaos import inject, storm_schedule
    from repro.core import engine

    rng = np.random.default_rng(JOB_SEED)
    jobs = job_stream_arrays(rng, n_jobs, deadline=DEADLINE,
                             workload_scale=WORKLOAD_SCALE)
    trace = paper_market(MARKET_SEED, **MARKET_KW)
    t0s = np.random.default_rng(JOB_SEED + 1).integers(
        0, len(trace) - DEADLINE - 1, n_jobs)
    pw, aw, preds = engine.prepare_noisy_inputs(
        trace, t0s, DEADLINE, NOISE_KIND, NOISE_LEVEL,
        JOB_SEED * 100003 + np.arange(n_jobs))
    sched = storm_schedule(FAULT_SEED, pw.shape[1], n_storms=n_storms,
                           storm_len=STORM_LEN, spike_mag=SPIKE_MAG,
                           pred_fault=PRED_FAULT)
    pw, aw, preds = inject(pw, aw, preds, sched)
    return jobs, pw, aw, preds, sched


def run() -> List[Row]:
    import jax

    from repro.chaos import FallbackConfig
    from repro.core import engine
    from repro.core.policy_pool import (KIND_AHAP, baseline_specs, paper_pool,
                                        rand_deadline_pool, specs_to_arrays)
    from repro.launch.mesh import make_pool_mesh, parse_pool_mesh_shape
    from repro.obs import pool_ledger, selection_ledger

    pool = paper_pool() + rand_deadline_pool() + baseline_specs()
    arrs = specs_to_arrays(pool)
    ahap = np.asarray(arrs["kind"]) == KIND_AHAP
    mesh = make_pool_mesh(
        shape=parse_pool_mesh_shape(os.environ.get("POOL_SIM_MESH", "")))
    # lam 0.5 arms the monitor within one storm slot and disarms within a
    # few clean ones — both edges land inside a 10-slot window
    configs = [FallbackConfig(threshold=t, lam=LAM) for t in THRESHOLDS]

    def select(inputs, fallback, collect=False):
        jobs, pw, aw, preds, _ = inputs
        return engine.simulate_and_select(
            arrs, jobs, PAPER_TPUT, pw, aw, preds, mesh=mesh,
            collect=collect, fallback=fallback)

    rows: List[Row] = []
    ledgers = {}
    gains: List[Tuple[int, float]] = []
    for n_storms in INTENSITY:
        inputs = build_inputs(n_storms)
        select(inputs, None)                      # warm-up pays compilation
        res_off, us_off = timed(select, inputs, None, repeat=max(REPEAT, 1))
        u_off = float(res_off.mean_utility[ahap].mean())
        rows.append((f"chaos_off__s{n_storms}", us_off, u_off))
        for cfg in configs:
            select(inputs, cfg)
            res_on, us_on = timed(select, inputs, cfg, repeat=max(REPEAT, 1))
            u_on = float(res_on.mean_utility[ahap].mean())
            rows.append(
                (f"chaos_on__s{n_storms}_thr{cfg.threshold:g}", us_on, u_on))
            if cfg is configs[0]:
                gains.append((n_storms, u_on - u_off))
                # flight-recorder pass OUTSIDE the timed runs, pinned
                # bitwise to the timed on-run (collect only ADDS outputs)
                res_c = select(inputs, cfg, collect=True)
                np.testing.assert_array_equal(res_c.mean_utility,
                                              res_on.mean_utility)
                led = pool_ledger(res_c.sim_out, inputs[0], PAPER_TPUT)
                sel = selection_ledger(res_c)
                ledgers[f"s{n_storms}"] = {"pool": led, "selection": sel}
                fb = led["fallback"]
                rows += [
                    (f"chaos_triggers__s{n_storms}", 0.0,
                     float(fb["triggers"])),
                    (f"chaos_recoveries__s{n_storms}", 0.0,
                     float(fb["recoveries"])),
                    (f"chaos_fallback_frac__s{n_storms}", 0.0,
                     fb["active_fraction"]),
                    (f"chaos_events_reconciled__s{n_storms}", 0.0,
                     float(fb["events_reconciled"])),
                    (f"chaos_selector_switches__s{n_storms}", 0.0,
                     float(sel["top_policy"]["n_switches"])),
                ]

    worst = max(INTENSITY)
    gain = dict(gains)[worst]
    rows.append((f"chaos_gain__s{worst}", 0.0, gain))

    extra = {
        "workload": {
            "jobs": N_JOBS, "slots": DEADLINE, "policies": len(pool),
            "ahap_lanes": int(ahap.sum()), "workload_scale": WORKLOAD_SCALE,
            "noise_kind": NOISE_KIND, "noise_level": NOISE_LEVEL,
        },
        "regime": {
            **MARKET_KW, "storm_len": STORM_LEN, "spike_mag": SPIKE_MAG,
            "pred_fault": PRED_FAULT, "intensity": list(INTENSITY),
            "thresholds": list(THRESHOLDS),
        },
        "gain_at_max_intensity": gain,
        "pool_mesh": "x".join(map(str, mesh.devices.shape)),
        "devices": jax.device_count(),
        "ledgers": ledgers,
    }
    merge_bench_rows(_JSON_PATH, "chaos", "chaos_sweep", rows, extra)
    if LEDGER_JSON:
        os.makedirs(os.path.dirname(LEDGER_JSON) or ".", exist_ok=True)
        with open(LEDGER_JSON, "w") as f:
            json.dump({"regime": extra["regime"], "ledgers": ledgers}, f,
                      indent=2)
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows

    print_rows(run())
