"""Spot market traces for the benchmark's traffic.

A copy of the program's synthetic Vast.ai-like market (``vast_like_trace``
and the evaluation regime ``paper_market`` that the benchmarks call), kept
here so that later changes to the program cannot change the traffic. It
draws bitwise what the program's generator draws for the same arguments
(``bench/tests/test_perfbench_gen.py`` pins that).

A 30-minute slot trace: a seasonal + AR(1) lognormal price and a negatively
correlated availability process, both driven by the same diurnal cycle.
"""
from __future__ import annotations

import numpy as np


def _ar1(rng, n, rho, sigma):
    x = np.zeros(n)
    e = rng.normal(0, sigma, n)
    for i in range(1, n):
        x[i] = rho * x[i - 1] + e[i]
    return x


# the program's defaults for every parameter a config may leave out
DEFAULTS = dict(
    days=10.0, slots_per_day=48, mean_price=0.45, price_sigma=0.32,
    price_season_amp=0.12, avail_mean=8.0, avail_season_amp=3.5,
    avail_sigma=2.0, avail_max=16, price_avail_corr=-0.5, rho=0.85,
    season_phase_slots=0.0,
)


def spot_trace(seed: int, **params):
    """``(prices (T,) f64, avail (T,) i64)`` for one market region.
    ``params`` override :data:`DEFAULTS` (a config's ``market`` block)."""
    unknown = set(params) - set(DEFAULTS)
    if unknown:
        raise ValueError(f"unknown market parameters: {sorted(unknown)}")
    p = dict(DEFAULTS, **params)
    rng = np.random.default_rng(seed)
    spd = int(p["slots_per_day"])
    n = int(p["days"] * spd)
    tod = 2 * np.pi * ((np.arange(n) - p["season_phase_slots"]) % spd) / spd
    season = np.cos(tod)  # +1 midnight .. -1 midday
    sig, rho = p["price_sigma"], p["rho"]
    z_price = _ar1(rng, n, rho, sig * np.sqrt(1 - rho**2))
    prices = p["mean_price"] * np.exp(
        p["price_season_amp"] * season + z_price - 0.5 * sig**2)
    prices = np.clip(prices, 0.02, 1.5)

    a_sig, corr = p["avail_sigma"], p["price_avail_corr"]
    z_av = _ar1(rng, n, rho, a_sig * np.sqrt(1 - rho**2))
    corr_term = corr * (z_price / max(sig, 1e-9)) * a_sig
    avail = (p["avail_mean"] - p["avail_season_amp"] * season
             + z_av * np.sqrt(1 - corr**2) + corr_term)
    avail = np.clip(np.round(avail), 0, p["avail_max"]).astype(np.int64)
    return prices.astype(np.float64), avail
