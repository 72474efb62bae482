"""The control of a cell's correctness check: the reference put in the
program's place and computed one precision lower (bfloat16 where the
configuration states float32). It plays a window of the cell's calls at
the cell's own size, every job of every call with the EG state carried from
call to call, and hands what it kept to ``harness.check``: the comparison a
run of the program gets, at the same limits. A sound check reads the
control as not correct.

    python3 bench/control.py --workload <cell> --seed <n> [--seed <m> ...]

Runs on the host alone. The window holds ``--calls`` calls, by default as
many as the check compares, so every call of it is compared. Prints one
JSON line per seed: the numbers, their limits and whether the control
passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.gen.traffic import Traffic, lane_arrays  # noqa: E402
from bench.ref import sched as ref  # noqa: E402

LOWER = {"float32": ml_dtypes.bfloat16, "float64": np.float32}
BLOCK = 250     # job rows the reference simulates at once


def control_window(traffic: Traffic, n_calls: int, low) -> list:
    """What a window of ``n_calls`` calls keeps for the check (see
    ``SelectDriver.keep``), with the reference in precision ``low`` as the
    program: every job's utility under every lane, then normalisation and
    the EG update from the state the previous call left."""
    cfg = traffic.config
    lanes = lane_arrays(traffic.lanes)
    tput = harness.tput_of(cfg)
    eta = harness.eg_eta(cfg, len(traffic.lanes))
    cu, ce = np.zeros(len(traffic.lanes), low), 0.0
    kept = []
    for c in range(n_calls):
        inp = traffic.call(c)
        u = []
        for lo in range(0, traffic.jobs_per_call, BLOCK):
            rows = np.arange(lo, min(lo + BLOCK, traffic.jobs_per_call))
            p, a, pm = harness.ref_inputs(traffic, inp, rows)
            jobs = {k: v[rows] for k, v in inp["jobs"].items()}
            u.append(ref.simulate_cells(jobs, lanes, tput, p, a, pm, ft=low))
        u = np.concatenate(u)
        w, cu, ce, regret = ref.eg_run(cu, ce, eta,
                                       ref.normalize(inp["jobs"], u), ft=low)
        f = lambda x: np.asarray(x, np.float64)
        kept.append({"u": u, "regret": float(regret),
                     "state": {"cu": f(cu), "ce": float(ce), "w": f(w)}})
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--calls", type=int, default=None,
                    help="calls in the control's window (default: as many "
                         "as the check compares)")
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload)
    low = LOWER[cell.config["precision"]]
    n_calls = args.calls or int(cell.traffic["check_calls"])
    for seed in args.seed:
        traffic = Traffic(cell.config, cell.traffic, seed)
        kept = control_window(traffic, n_calls, low)
        numbers = harness.check(traffic, kept, harness.pick(traffic, n_calls))
        correct, rows = harness.judge(numbers, cell.limits)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "calls": n_calls, "control_correct": correct,
                          "numbers": numbers, "checks": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
