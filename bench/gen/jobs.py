"""Job draws for the benchmark's traffic.

A copy of the program's Fig. 9 job distribution
(``benchmarks.common.job_stream_arrays``): one vectorized draw per field, in
the same order, so the same generator state gives bitwise the same jobs
(``bench/tests/test_perfbench_gen.py`` pins that). Fields are returned as a
dict of numpy arrays with the program's dtypes.
"""
from __future__ import annotations

import numpy as np

FIELDS = ("workload", "deadline", "n_min", "n_max", "value", "gamma", "p_o")


def draw_jobs(rng: np.random.Generator, n: int, spec: dict) -> dict:
    """``n`` jobs from a config's ``jobs`` block: ``workload`` uniform on
    ``[lo, hi)``, ``n_min`` and ``n_max`` integers on ``[lo, hi)``, the rest
    constant. A field given as a single number is constant (no draw)."""
    def ranged(key, dtype, draw):
        v = spec[key]
        if isinstance(v, (list, tuple)):
            return draw(*v).astype(dtype)
        return np.full(n, v, dtype)

    workload = ranged("workload", np.float32,
                      lambda lo, hi: rng.uniform(lo, hi, n))
    deadline = np.full(n, spec["deadline"], np.int32)
    n_min = ranged("n_min", np.int32, lambda lo, hi: rng.integers(lo, hi, n))
    n_max = ranged("n_max", np.int32, lambda lo, hi: rng.integers(lo, hi, n))
    return {
        "workload": workload,
        "deadline": deadline,
        "n_min": n_min,
        "n_max": n_max,
        "value": np.full(n, spec["value"], np.float32),
        "gamma": np.full(n, spec["gamma"], np.float32),
        "p_o": np.full(n, spec["on_demand_price"], np.float32),
    }
