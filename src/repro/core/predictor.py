"""Spot price/availability forecasting (paper Sec. II-C, Fig. 3).

Every predictor produces a *prediction matrix* P[t, j, c]: the forecast made
at slot t for slot t+j (j=0 is the observed present, always exact), with
channels c=0 price, c=1 availability. The matrix form is what the vmapped
policy simulator consumes.

Predictors:
  PerfectPredictor  — oracle (paper's 'Perfect-Predictor' strategy)
  NoisyPredictor    — the paper's four noise regimes: {magnitude-dependent,
                      fixed-magnitude} x {uniform, heavy-tail}, with error
                      growing in the prediction step j (multi-step error
                      accumulation, Definition 1)
  ARIMAPredictor    — seasonally-differenced AR(p) fit by least squares on a
                      rolling history window (the paper's ARIMA with 30-min
                      slots), forecast recursively
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.market import Trace, require_finite
from repro.obs import counters

NOISE_KINDS = (
    "magdep_uniform",
    "fixed_uniform",
    "magdep_heavytail",
    "fixed_heavytail",
)


def _true_future(trace: Trace, horizon: int) -> np.ndarray:
    """(T, horizon+1, 2) true values, edge-padded past the end."""
    T = len(trace)
    prices = np.concatenate([trace.prices, np.full(horizon, trace.prices[-1])])
    avail = np.concatenate([trace.avail, np.full(horizon, trace.avail[-1])])
    out = np.empty((T, horizon + 1, 2))
    for j in range(horizon + 1):
        out[:, j, 0] = prices[j : j + T]
        out[:, j, 1] = avail[j : j + T]
    return out


def true_future_batch(prices: np.ndarray, avail: np.ndarray,
                      horizon: int) -> np.ndarray:
    """Batched :func:`_true_future`: (K, T) price/avail windows ->
    (K, T, horizon+1, 2) true values, each row edge-padded past its end."""
    prices = np.asarray(prices, float)
    avail = np.asarray(avail, float)
    T = prices.shape[1]
    p = np.concatenate([prices, np.repeat(prices[:, -1:], horizon, axis=1)], 1)
    a = np.concatenate([avail, np.repeat(avail[:, -1:], horizon, axis=1)], 1)
    idx = np.arange(T)[:, None] + np.arange(horizon + 1)[None, :]
    return np.stack([p[:, idx], a[:, idx]], axis=-1)


# numpy's SeedSequence hash constants (numpy/random/bit_generator.pyx)
_SS_INIT_A = 0x43B0D7E5
_SS_MULT_A = 0x931E8875
_SS_INIT_B = 0x8B51F9DD
_SS_MULT_B = 0x58F38DED
_SS_MIX_L = 0xCA01F9DD
_SS_MIX_R = 0x4973F715
_SS_POOL = 4
_M32 = (1 << 32) - 1
# PCG64's 128-bit LCG multiplier (numpy/random/src/pcg64/pcg64.h)
_PCG64_MULT = 0x2360ED051FC65DA44385DF649FCCF645
_M128 = (1 << 128) - 1

# Rows from which noisy_matrix_batch seeds its generators in one batched
# pass: below it, the pass's fixed cost (about 0.2 ms on a TPU v5e host's
# CPU) outweighs the per-row ``default_rng`` constructions it replaces;
# there the two paths cross between 12 and 16 rows (PERF.md §6, PR 15).
BATCHED_SEEDING_MIN_ROWS = 16


def _hashmix_consts(n: int, init: int, mult: int) -> list:
    """The (xor, multiply) constants of ``n`` successive SeedSequence
    hashmix steps: they depend on the step, not on the seed."""
    out, h = [], init
    for _ in range(n):
        x = h
        h = (h * mult) & _M32
        out.append((np.uint32(x), np.uint32(h)))
    return out


# mix_entropy's 4 pool fills and 12 cross mixes, generate_state's 8 words
_MIX_CONSTS = _hashmix_consts(_SS_POOL * _SS_POOL, _SS_INIT_A, _SS_MULT_A)
_GEN_CONSTS = _hashmix_consts(2 * _SS_POOL, _SS_INIT_B, _SS_MULT_B)


def _hashmix(value: np.ndarray, c) -> np.ndarray:
    value = (value ^ c[0]) * c[1]
    return value ^ (value >> np.uint32(16))


def seed_sequence_state(seeds) -> np.ndarray:
    """``np.random.SeedSequence(s).generate_state(4, np.uint64)`` for every
    seed of an integer array at once: (K,) -> (K, 4) uint64, bitwise.

    numpy's entropy mixing is uint32 arithmetic whose constants do not
    depend on the seed, so it runs over the whole seed axis. A seed in
    [0, 2**64) is one or two 32-bit entropy words; a missing second word
    hashes as a zero word would, since the pool holds four. Negative seeds
    raise the ``ValueError`` that ``default_rng`` raises."""
    seeds = np.asarray(seeds)
    if seeds.dtype.kind not in "iu":
        raise TypeError(f"seeds must be integers, not {seeds.dtype}")
    if seeds.dtype.kind == "i" and seeds.size and seeds.min() < 0:
        raise ValueError("expected non-negative integer")
    s = seeds.astype(np.uint64).reshape(-1)
    words = [(s & np.uint64(_M32)).astype(np.uint32),
             (s >> np.uint64(32)).astype(np.uint32)]
    words += [np.zeros_like(words[0])] * (_SS_POOL - 2)
    consts = iter(_MIX_CONSTS)
    pool = [_hashmix(w, next(consts)) for w in words]
    mix_l, mix_r = np.uint32(_SS_MIX_L), np.uint32(_SS_MIX_R)
    for i_src in range(_SS_POOL):
        for i_dst in range(_SS_POOL):
            if i_src != i_dst:
                r = (mix_l * pool[i_dst]
                     - mix_r * _hashmix(pool[i_src], next(consts)))
                pool[i_dst] = r ^ (r >> np.uint32(16))
    state = np.stack([_hashmix(pool[i % _SS_POOL], c)
                      for i, c in enumerate(_GEN_CONSTS)], axis=1)
    lo, hi = state[:, 0::2].astype(np.uint64), state[:, 1::2]
    out = lo | (hi.astype(np.uint64) << np.uint64(32))
    return out.reshape(seeds.shape + (_SS_POOL,))


def pcg64_states(seeds) -> list:
    """``np.random.PCG64(s).state["state"]`` for every seed of an integer
    array: each row's ``(state, inc)`` as Python ints, from
    :func:`seed_sequence_state`'s words through PCG64's seeding (two LCG
    steps)."""
    out = []
    for w0, w1, w2, w3 in seed_sequence_state(seeds).reshape(-1, 4).tolist():
        inc = (((w2 << 64 | w3) << 1) | 1) & _M128
        state = ((inc + (w0 << 64 | w1)) * _PCG64_MULT + inc) & _M128
        out.append((state, inc))
    return out


def _noise_draws(seeds: np.ndarray, kind: str, shape: tuple) -> np.ndarray:
    """(K,) + ``shape`` raw draws, row k from the stream
    ``np.random.default_rng(seeds[k])`` gives: ``uniform(-1, 1, shape)``
    for the uniform kinds, ``standard_t(3, shape)`` for the heavy-tail
    ones. Counts the rows, and those seeded by the batched pass, in
    ``repro.obs.counters`` (``forecast_rows``, ``forecast_rows_batched``)."""
    eps = np.empty((len(seeds),) + tuple(shape))
    uniform = kind.endswith("uniform")

    def draw(gen, row):
        if uniform:  # uniform(-1, 1) is -1 + 2 * this draw
            gen.random(out=row)
        else:
            row[...] = gen.standard_t(3, shape)

    batched = (len(seeds) >= BATCHED_SEEDING_MIN_ROWS
               and seeds.dtype.kind in "iu")
    counters.add("forecast_rows", len(seeds))
    counters.add("forecast_rows_batched", len(seeds) if batched else 0)
    if batched:
        bits = np.random.PCG64(0)
        gen = np.random.Generator(bits)
        for row, (state, inc) in zip(eps, pcg64_states(seeds)):
            bits.state = {"bit_generator": "PCG64",
                          "state": {"state": state, "inc": inc},
                          "has_uint32": 0, "uinteger": 0}
            draw(gen, row)
    else:
        for row, s in zip(eps, seeds):
            draw(np.random.default_rng(int(s)), row)
    if uniform:
        eps *= 2.0
        eps -= 1.0
    return eps


def noisy_matrix_batch(prices: np.ndarray, avail: np.ndarray, kind: str,
                       level, seeds, horizon: int,
                       avail_max: int = 16) -> np.ndarray:
    """Batched :class:`NoisyPredictor`: the whole (K, T, horizon+1, 2)
    forecast stack in one vectorized pass over (K, T) market windows.

    Bitwise-equal to stacking
    ``NoisyPredictor(window_k, kind, level, seed=seeds[k]).matrix(horizon)``
    over k (pinned in tests/test_selection_engine.py and
    tests/test_forecast_seeding.py): every arithmetic op is elementwise
    over the batch axis, and each row's noise is drawn from the stream
    ``np.random.default_rng(seeds[k])`` would give. From
    :data:`BATCHED_SEEDING_MIN_ROWS` integer seeds up, the K streams are
    seeded in one batched pass (:func:`pcg64_states`) and drawn by one
    reused generator whose state is set per row; below it, each row
    constructs its own ``default_rng``. The draw itself stays numpy's, once
    per row (independent streams have no batch API).

    ``level`` may be a scalar (one noise level for every row) or a (K,)
    array of per-row levels — how the scenario grid realizes its
    prediction-noise axis inside one batched call; row k then matches the
    per-job construction at ``level[k]`` (level 0 rows reduce to the
    perfect forecast)."""
    assert kind in NOISE_KINDS, kind
    prices = np.asarray(prices, float)
    avail = np.asarray(avail, float)
    require_finite("prices", prices)
    require_finite("avail", avail)
    require_finite("level", np.asarray(level, float))
    seeds = np.asarray(seeds)
    out = true_future_batch(prices, avail, horizon)
    K = out.shape[0]
    assert seeds.shape == (K,), (seeds.shape, K)
    level = np.asarray(level, float)
    if level.ndim == 0:
        scale = level * np.sqrt(np.arange(horizon + 1))          # 0 at j=0
    else:
        assert level.shape == (K,), (level.shape, K)
        scale = level[:, None] * np.sqrt(np.arange(horizon + 1))  # (K, h+1)
    ref = np.stack([
        np.broadcast_to(prices.mean(axis=1)[:, None], prices.shape),
        np.broadcast_to(avail.mean(axis=1)[:, None], avail.shape),
    ], axis=-1)  # (K, T, 2) per-row reference magnitudes
    eps = _noise_draws(seeds, kind, out.shape[1:])
    if not kind.endswith("uniform"):  # heavy-tail: Student-t(3), clipped
        eps = np.clip(eps, -8, 8) / np.sqrt(3)
    if scale.ndim == 1:
        eps = eps * scale[None, None, :, None]
    else:
        eps = eps * scale[:, None, :, None]
    if kind.startswith("magdep"):
        noisy = out * (1.0 + eps)
    else:
        noisy = out + eps * ref[:, :, None, :]
    noisy[..., 0] = np.clip(noisy[..., 0], 0.01, 10.0)
    noisy[..., 1] = np.clip(np.round(noisy[..., 1]), 0, avail_max)
    noisy[:, :, 0, :] = out[:, :, 0, :]  # the present is observed
    return noisy


def _true_future_batch_jax(prices, avail, horizon: int):
    """Device twin of :func:`true_future_batch`: (K, T) jnp windows ->
    (K, T, horizon+1, 2) edge-padded true values, all on device."""
    T = prices.shape[1]
    p = jnp.concatenate(
        [prices, jnp.repeat(prices[:, -1:], horizon, axis=1)], axis=1)
    a = jnp.concatenate(
        [avail, jnp.repeat(avail[:, -1:], horizon, axis=1)], axis=1)
    idx = jnp.arange(T)[:, None] + jnp.arange(horizon + 1)[None, :]
    return jnp.stack([p[:, idx], a[:, idx]], axis=-1)


@functools.partial(jax.jit, static_argnames=("kind", "horizon", "avail_max"))
def _noisy_matrix_batch_jax(prices, avail, level, seeds, kind: str,
                            horizon: int, avail_max: int):
    out = _true_future_batch_jax(prices, avail, horizon)
    steps = jnp.sqrt(jnp.arange(horizon + 1, dtype=jnp.float32))
    scale = level * steps if level.ndim == 0 else level[:, None] * steps
    ref = jnp.stack([
        jnp.broadcast_to(jnp.mean(prices, axis=1)[:, None], prices.shape),
        jnp.broadcast_to(jnp.mean(avail, axis=1)[:, None], avail.shape),
    ], axis=-1)                                     # (K, T, 2)
    shape = out.shape[1:]

    def draw(seed):
        key = jax.random.PRNGKey(seed)
        if kind.endswith("uniform"):
            return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
        return (jnp.clip(jax.random.t(key, 3.0, shape, jnp.float32),
                         -8.0, 8.0)
                / np.sqrt(3.0).astype(np.float32))

    eps = jax.vmap(draw)(seeds)                     # (K, T, h+1, 2)
    eps = eps * (scale[None, None, :, None] if scale.ndim == 1
                 else scale[:, None, :, None])
    if kind.startswith("magdep"):
        noisy = out * (1.0 + eps)
    else:
        noisy = out + eps * ref[:, :, None, :]
    noisy = jnp.stack([
        jnp.clip(noisy[..., 0], 0.01, 10.0),
        jnp.clip(jnp.round(noisy[..., 1]), 0.0, float(avail_max)),
    ], axis=-1)
    return noisy.at[:, :, 0, :].set(out[:, :, 0, :])  # present is observed


def noisy_matrix_batch_jax(prices, avail, kind: str, level, seeds,
                           horizon: int, avail_max: int = 16):
    """Jitted device twin of :func:`noisy_matrix_batch`: the whole
    (K, T, horizon+1, 2) noisy forecast stack built by one batched-PRNG
    XLA program — no host loop over per-seed generator objects, and the
    result is born on device where the pool simulator consumes it
    (core.engine's ``prep_backend="jax"``).

    Same math (sqrt(j) error growth, per-row reference magnitudes, clips,
    observed-present restore) in float32, but the draws come from JAX's
    counter-based PRNG keyed per row on ``seeds`` — NOT bitwise-equal to
    the numpy Philox streams. The numpy path stays the parity oracle:
    tests pin that both backends agree on the selected winner and keep
    EG regret within the Theorem 2 bound (tests/test_region_engine.py).
    """
    assert kind in NOISE_KINDS, kind
    prices = jnp.asarray(prices, jnp.float32)
    avail = jnp.asarray(avail, jnp.float32)
    seeds = jnp.asarray(seeds, jnp.uint32)
    assert seeds.shape == (prices.shape[0],), (seeds.shape, prices.shape)
    return _noisy_matrix_batch_jax(
        prices, avail, jnp.asarray(level, jnp.float32), seeds,
        kind, int(horizon), int(avail_max),
    )


def regional_noisy_matrix_jax(prices, avail, kind: str, level, seeds,
                              horizon: int, avail_max: int = 16):
    """:class:`RegionalPredictor` lift of :func:`noisy_matrix_batch_jax`:
    (K, R, T) per-(job, region) market windows and (K, R) seeds ->
    (K, R, T, horizon+1, 2) forecast stacks, built by ONE jitted call over
    the flattened (K*R,) row axis — the region axis never leaves the
    device. ``level`` is a scalar or (K,) per-job array (broadcast across
    that job's regions)."""
    prices = jnp.asarray(prices, jnp.float32)
    K, R, T = prices.shape
    avail = jnp.asarray(avail, jnp.float32)
    seeds = jnp.asarray(seeds, jnp.uint32)
    assert seeds.shape == (K, R), (seeds.shape, (K, R))
    level = jnp.asarray(level, jnp.float32)
    if level.ndim:
        level = jnp.repeat(level, R)                # (K*R,) per-row levels
    out = noisy_matrix_batch_jax(
        prices.reshape(K * R, T), avail.reshape(K * R, T), kind, level,
        seeds.reshape(-1), horizon, avail_max,
    )
    return out.reshape(K, R, T, horizon + 1, 2)


class PerfectPredictor:
    def __init__(self, trace: Trace):
        self.trace = trace

    def matrix(self, horizon: int) -> np.ndarray:
        return _true_future(self.trace, horizon)


class NoisyPredictor:
    """Perfect forecast corrupted by one of the four paper noise regimes.

    ``level`` is the relative error scale (e.g. 0.1 = 10%); the j-step error
    scales with sqrt(j) (error accumulation in multi-step forecasts).
    """

    def __init__(self, trace: Trace, kind: str, level: float, seed: int = 0,
                 avail_max: int = 16):
        assert kind in NOISE_KINDS, kind
        self.trace, self.kind, self.level, self.seed = trace, kind, level, seed
        self.avail_max = avail_max

    def matrix(self, horizon: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed)
        out = _true_future(self.trace, horizon)
        T = out.shape[0]
        scale = self.level * np.sqrt(np.arange(horizon + 1))  # 0 at j=0
        ref = np.stack([
            np.full(T, np.mean(self.trace.prices)),
            np.full(T, np.mean(self.trace.avail)),
        ], axis=-1)  # (T,2) reference magnitudes for fixed-magnitude noise
        if self.kind.endswith("uniform"):
            eps = rng.uniform(-1, 1, out.shape)
        else:  # heavy-tail: Student-t(3), clipped for sanity
            eps = np.clip(rng.standard_t(3, out.shape), -8, 8) / np.sqrt(3)
        eps = eps * scale[None, :, None]
        if self.kind.startswith("magdep"):
            noisy = out * (1.0 + eps)
        else:
            noisy = out + eps * ref[:, None, :]
        noisy[..., 0] = np.clip(noisy[..., 0], 0.01, 10.0)
        noisy[..., 1] = np.clip(np.round(noisy[..., 1]), 0, self.avail_max)
        noisy[:, 0, :] = out[:, 0, :]  # the present is observed, not predicted
        return noisy


@dataclass
class ARIMAConfig:
    p: int = 2                 # AR order on deseasonalized residuals
    seasonal_lag: int = 48     # one day of 30-min slots
    history: int = 10 * 48     # fit window
    ridge: float = 1e-3


class ARIMAPredictor:
    """Seasonal AR: y_t = m_{t mod s} + r_t with AR(p) residuals.

    The seasonal profile m (per time-of-day mean over the history window)
    captures the diurnal cycle; the residual AR(p) (numpy lstsq with ridge)
    captures the persistent noise — a SARIMA-family decomposition that beats
    both pure persistence and naive seasonal differencing on AR-dominated
    diurnal traces (test_market_predictor.py pins this).
    """

    def __init__(self, trace: Trace, cfg: Optional[ARIMAConfig] = None,
                 avail_max: int = 16):
        self.trace = trace
        self.cfg = cfg or ARIMAConfig(seasonal_lag=trace.slots_per_day)
        self.avail_max = avail_max

    def _fit_forecast(self, series: np.ndarray, t: int, horizon: int) -> np.ndarray:
        c = self.cfg
        s, p = c.seasonal_lag, c.p
        start = max(0, t + 1 - c.history)
        hist = series[start : t + 1]
        if len(hist) < s + p + 8:  # not enough data: persistence forecast
            return np.full(horizon, series[t])
        logspace = bool(np.all(hist > 0))  # prices: multiplicative dynamics
        h = np.log(hist) if logspace else hist.astype(float)
        # smoothed seasonal profile over the history window
        idx = (np.arange(start, t + 1)) % s
        prof = np.full(s, h.mean())
        for k in range(s):
            sel = h[idx == k]
            if len(sel):
                prof[k] = sel.mean()
        w = 5  # circular smoothing kills per-slot profile noise
        ker = np.ones(w) / w
        prof = np.convolve(np.concatenate([prof[-w:], prof, prof[:w]]), ker, "same")[w:-w]
        r = h - prof[idx]
        # AR(p) on deseasonalized residuals
        X = np.stack([r[p - i - 1 : len(r) - i - 1] for i in range(p)], axis=1)
        y = r[p:]
        A = X.T @ X + c.ridge * len(y) * np.eye(p)
        coef = np.linalg.solve(A, X.T @ y)
        rbuf = list(r[-p:])  # oldest..newest
        out = np.empty(horizon)
        for j in range(1, horizon + 1):
            rn = float(np.dot(coef, rbuf[::-1][:p]))
            v = prof[(t + j) % s] + rn
            out[j - 1] = np.exp(v) if logspace else v
            rbuf.append(rn)
        return out

    def matrix(self, horizon: int) -> np.ndarray:
        T = len(self.trace)
        out = _true_future(self.trace, horizon)  # j=0 column = observed
        for t in range(T):
            fp = self._fit_forecast(self.trace.prices, t, horizon)
            fa = self._fit_forecast(self.trace.avail.astype(float), t, horizon)
            out[t, 1:, 0] = np.clip(fp, 0.01, 10.0)
            out[t, 1:, 1] = np.clip(np.round(fa), 0, self.avail_max)
        return out


class RegionalPredictor:
    """Per-region predictor lifted to a multi-region market: ``matrix``
    returns (R, T, horizon+1, 2) — one prediction matrix per region, each
    produced by an independent base predictor.

    ``factory(trace, region_index) -> predictor`` builds the per-region base
    (default: PerfectPredictor). The region index lets noisy/ARIMA factories
    decorrelate seeds across regions, e.g.::

        RegionalPredictor(market,
                          lambda tr, r: NoisyPredictor(tr, "fixed_uniform",
                                                       0.2, seed=r))
    """

    def __init__(self, market, factory=None):
        self.market = market
        self.factory = factory or (lambda tr, r: PerfectPredictor(tr))
        self.predictors = [
            self.factory(market.region(r), r) for r in range(market.n_regions)
        ]

    def matrix(self, horizon: int) -> np.ndarray:
        return np.stack([p.matrix(horizon) for p in self.predictors])


def mape(pred: np.ndarray, true: np.ndarray) -> float:
    return float(np.mean(np.abs(pred - true) / np.maximum(np.abs(true), 1e-6)))


def forecast_errors(trace: Trace, predictor, horizon: int) -> dict:
    """Per-step MAPE for price and availability (benchmarks/fig3)."""
    M = predictor.matrix(horizon)
    truth = _true_future(trace, horizon)
    out = {"price": [], "avail": []}
    T = len(trace)
    for j in range(1, horizon + 1):
        valid = np.arange(T - j)
        out["price"].append(mape(M[valid, j, 0], truth[valid, j, 0]))
        out["avail"].append(mape(M[valid, j, 1], np.maximum(truth[valid, j, 1], 1)))
    return out
