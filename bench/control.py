"""The control of a cell's correctness check: the reference put in the
program's place and computed one precision lower (bfloat16 where the
configuration states float32). The entry the cell's configuration names
plays a window of the cell's calls at the cell's own size
(``control_window``) and hands what it kept to its own check: the
comparison a run of the program gets, at the same limits. A sound check
reads the control as not correct.

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

LOWER = {"float32": ml_dtypes.bfloat16, "float64": np.float32}


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--calls", type=int, default=None,
                    help="calls in the control's window (default: as many "
                         "as the check compares)")
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload, root)
    low = LOWER[cell.config["precision"]]
    n_calls = args.calls or int(cell.traffic["check_calls"])
    for seed in args.seed:
        traffic = cell.entry.Traffic(cell.config, cell.traffic, seed)
        kept = cell.entry.control_window(traffic, n_calls, low)
        numbers = cell.entry.check(traffic, kept,
                                   harness.pick(traffic, n_calls))
        correct, rows = harness.judge(numbers, cell.limits)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "calls": n_calls, "control_correct": correct,
                          "numbers": numbers, "checks": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
