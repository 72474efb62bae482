"""Reference forecasts: the paper's noisy predictor, written out plainly.

The forecast made at slot t for slot t+j (j = 0 is the observed present) is
the true value plus noise whose scale grows with sqrt(j) (Sec. II-C,
Definition 1). For the fixed-magnitude kinds the noise is relative to the
window's mean price and mean availability; prices are clipped to [0.01, 10]
and availability is rounded and clipped to [0, avail_max]. Each job's noise
comes from ``np.random.default_rng(seed)`` drawn over the whole
(T, horizon + 1, 2) forecast block, which is the convention the program's
forecast stack documents.
"""
from __future__ import annotations

import numpy as np


def forecast(prices, avail, kind: str, level: float, seed: int,
             horizon: int, avail_max: int = 16) -> np.ndarray:
    """(T, horizon + 1, 2) forecast for one job's (T,) market window."""
    prices = np.asarray(prices, float)
    avail = np.asarray(avail, float)
    t_len = len(prices)
    true = np.empty((t_len, horizon + 1, 2))
    for j in range(horizon + 1):
        # past the window's end the last value is held
        idx = np.minimum(np.arange(t_len) + j, t_len - 1)
        true[:, j, 0] = prices[idx]
        true[:, j, 1] = avail[idx]
    rng = np.random.default_rng(int(seed))
    if kind.endswith("uniform"):
        eps = rng.uniform(-1, 1, true.shape)
    elif kind.endswith("heavytail"):
        eps = np.clip(rng.standard_t(3, true.shape), -8, 8) / np.sqrt(3)
    else:
        raise ValueError(f"unknown noise kind {kind!r}")
    eps = eps * (level * np.sqrt(np.arange(horizon + 1)))[None, :, None]
    if kind.startswith("magdep"):
        noisy = true * (1.0 + eps)
    elif kind.startswith("fixed"):
        mag = np.array([prices.mean(), avail.mean()])
        noisy = true + eps * mag[None, None, :]
    else:
        raise ValueError(f"unknown noise kind {kind!r}")
    noisy[..., 0] = np.clip(noisy[..., 0], 0.01, 10.0)
    noisy[..., 1] = np.clip(np.round(noisy[..., 1]), 0, avail_max)
    noisy[:, 0, :] = true[:, 0, :]
    return noisy
