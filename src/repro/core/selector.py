"""Online Policy Selection (Algorithm 2): Exponentiated Gradient over the
policy pool, full-information (every candidate's utility is evaluated per
job — cheap thanks to the vmapped simulator).

Guarantee (Theorem 2): with eta = sqrt(2 ln M / K) and utilities normalized
to [0,1], regret vs the best fixed policy is <= sqrt(2 K ln M).
benchmarks/theorem2.py verifies the bound empirically; test_selector.py
asserts it for adversarial utility streams.

Two implementations share the update rule:

* ``init_selector``/``update`` — the numpy reference, one job at a time
  (the paper's online formulation, and the parity oracle).
* ``eg_init``/``run_eg_scan`` — a jitted ``lax.scan`` over a whole (K, M)
  normalized-utility matrix, producing the final state plus per-job
  max-weight / regret trajectories (and, optionally, the full weight
  history) in ONE device call. Same update order, same clipping, same
  first-max argmax ties as the numpy loop (pinned to float32 tolerance in
  tests/test_selection_engine.py). This is what core.engine chains after
  the sharded pool simulator so the (K, M) matrix never round-trips
  through host numpy.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclass
class SelectorState:
    weights: np.ndarray               # (M,) simplex
    eta: float
    k: int = 0
    cum_expected: float = 0.0         # sum_k E_{w_k}[u_k]
    cum_utils: Optional[np.ndarray] = None  # (M,) per-policy cumulative
    weight_history: List[np.ndarray] = field(default_factory=list)
    # record every history_stride-th update (plus the initial weights): the
    # per-update (M,) copies are O(K*M) host memory at engine scale
    history_stride: int = 1


def default_eta(n_policies: int, horizon: int) -> float:
    """Theorem 2's learning rate: sqrt(2 ln M / K)."""
    return float(np.sqrt(2.0 * np.log(n_policies) / max(horizon, 1)))


def init_selector(n_policies: int, horizon: int, eta: Optional[float] = None,
                  track_history: bool = False,
                  history_stride: int = 1) -> SelectorState:
    eta = default_eta(n_policies, horizon) if eta is None else eta
    if history_stride < 1:
        raise ValueError(f"history_stride must be >= 1, got {history_stride}")
    st = SelectorState(
        weights=np.full(n_policies, 1.0 / n_policies),
        eta=eta,
        cum_utils=np.zeros(n_policies),
        history_stride=history_stride,
    )
    if track_history:
        st.weight_history.append(st.weights.copy())
    return st


def select(state: SelectorState, rng: np.random.Generator) -> int:
    """Sample the policy to run for the incoming job (Line 6)."""
    return int(rng.choice(len(state.weights), p=state.weights))


def update(state: SelectorState, utilities: np.ndarray,
           track_history: bool = False) -> SelectorState:
    """EG / multiplicative-weights update (Lines 7-11). ``utilities`` must be
    normalized to [0, 1] (see repro.core.job.normalize_utility)."""
    u = np.clip(np.asarray(utilities, float), 0.0, 1.0)
    assert u.shape == state.weights.shape
    state.cum_expected += float(np.dot(state.weights, u))
    state.cum_utils += u
    logits = np.log(np.maximum(state.weights, 1e-300)) + state.eta * u
    logits -= logits.max()
    w = np.exp(logits)
    state.weights = w / w.sum()
    state.k += 1
    if track_history and state.k % state.history_stride == 0:
        state.weight_history.append(state.weights.copy())
    return state


def regret(state) -> float:
    """max_m sum_k u_k^m - sum_k E_{w_k}[u_k]  (cumulative, Theorem 2 LHS).
    Accepts SelectorState and EGState alike (same field names)."""
    return float(state.cum_utils.max() - state.cum_expected)


def regret_bound(n_policies: int, k: int) -> float:
    return float(np.sqrt(2.0 * k * np.log(n_policies)))


def best_policy(state) -> int:
    return int(np.argmax(state.weights))


def sample_policies(state_or_weights, n: int,
                    rng: np.random.Generator) -> np.ndarray:
    """``n`` i.i.d. draws from the selector distribution — Line 6 of
    Alg. 2 vectorized for fleet admission (one policy per arriving job).
    Accepts a SelectorState/EGState or a bare weight vector; weights are
    renormalized in f64 (the device state is f32)."""
    w = np.asarray(getattr(state_or_weights, "weights", state_or_weights),
                   np.float64)
    w = np.maximum(w, 0.0)
    w = w / w.sum()
    return rng.choice(len(w), size=int(n), p=w)


# ---------------------------------------------------------------------------
# Device-resident EG: jitted lax.scan over a (K, M) utility matrix
# ---------------------------------------------------------------------------

class EGState(NamedTuple):
    """Selector state as f32 device leaves — field names mirror
    SelectorState so ``regret``/``best_policy`` work on both."""
    weights: jnp.ndarray        # (M,) simplex
    eta: jnp.ndarray            # f32 scalar
    k: jnp.ndarray              # i32 scalar, updates applied so far
    cum_expected: jnp.ndarray   # f32 scalar
    cum_utils: jnp.ndarray      # (M,)
    # Kahan compensations of the two sums: regret is their small difference
    # (~4 at 1000 jobs against sums of ~800), so plain f32 accumulation
    # would cost it ~3e-3 relative
    cum_expected_c: jnp.ndarray  # f32 scalar
    cum_utils_c: jnp.ndarray     # (M,)


def eg_init(n_policies: int, horizon: int,
            eta: Optional[float] = None) -> EGState:
    """Device twin of :func:`init_selector` (uniform weights, Thm. 2 eta)."""
    eta = default_eta(n_policies, horizon) if eta is None else float(eta)
    return EGState(
        weights=jnp.full((n_policies,), 1.0 / n_policies, jnp.float32),
        eta=jnp.float32(eta),
        k=jnp.int32(0),
        cum_expected=jnp.float32(0.0),
        cum_utils=jnp.zeros((n_policies,), jnp.float32),
        cum_expected_c=jnp.float32(0.0),
        cum_utils_c=jnp.zeros((n_policies,), jnp.float32),
    )


def _kahan_add(total, comp, x):
    """One step of Kahan summation: ``(total + x, new compensation)``."""
    y = x - comp
    t = total + y
    return t, (t - total) - y


@functools.partial(jax.jit, static_argnames=("track_history", "collect"))
def run_eg_scan(state: EGState, utilities, track_history: bool = False,
                collect: bool = False):
    """Run the EG update over every row of ``utilities`` ((K, M), normalized
    to [0, 1] — clipped here exactly like the numpy loop) in one
    ``lax.scan``. Returns ``(final_state, traj)`` where ``traj`` holds the
    per-job post-update trajectories:

      max_weight  (K,)  max_m w_k[m] — iters-to-half-weight reads off this
      regret      (K,)  max_m cum_utils - cum_expected after job k
      weights     (K, M) only when ``track_history`` (Fig. 10's heatmap)
      entropy     (K,)  only when ``collect`` — Shannon entropy of w_k,
                        the flight recorder's convergence gauge
      top_policy  (K,)  only when ``collect`` — argmax_m w_k[m] (first-max
                        ties, matching the numpy loop)

    Both static flags only ADD scan outputs, so the default call compiles
    to the identical program. The update order, the clipping, and
    first-max argmax ties match :func:`update`; the weights are recomputed
    each step as ``softmax(eta * cum_utils)``, which is the multiplicative
    update from :func:`eg_init`'s uniform weights. Chain calls by passing
    the returned state back in: the scan is associative over concatenated
    utility chunks, which is what
    core.engine's job-chunked streaming mode relies on — for both the
    single-region and the regional engine path: the scan is agnostic to
    where the (K, M) utilities came from (``simulate_pool_jobs`` on a flat
    or a regional market), which is why R == 1 engine runs are
    bitwise-identical end to end."""
    u_all = jnp.clip(jnp.asarray(utilities, jnp.float32), 0.0, 1.0)
    tiny = jnp.float32(np.finfo(np.float32).tiny)

    def step(s: EGState, u):
        # multiply-then-reduce, not jnp.dot: XLA:CPU rounds a dot fusion
        # differently from one program to the next, which would break the
        # bitwise chunked == unchunked guarantee
        ce, ce_c = _kahan_add(s.cum_expected, s.cum_expected_c,
                              jnp.sum(s.weights * u))
        cu, cu_c = _kahan_add(s.cum_utils, s.cum_utils_c, u)
        # from eg_init's uniform start the multiplicative update gives
        # w_k ~ exp(eta * cum_utils_k); taking it from the sums, not from
        # log(w_{k-1}), keeps transcendental error from compounding over
        # the stream (the TPU's f32 log is biased by ~100 ulp on average)
        logits = s.eta * (cu - cu.max())
        w = jnp.exp(logits)
        w = w / w.sum()
        ns = EGState(w, s.eta, s.k + 1, ce, cu, ce_c, cu_c)
        ys = {"max_weight": w.max(), "regret": cu.max() - ce}
        if track_history:
            ys["weights"] = w
        if collect:
            ys["entropy"] = -jnp.sum(w * jnp.log(jnp.maximum(w, tiny)))
            ys["top_policy"] = jnp.argmax(w).astype(jnp.int32)
        return ns, ys

    return jax.lax.scan(step, state, u_all)


def iters_to_half(max_weight: np.ndarray) -> int:
    """First 1-based update index where the leader's weight exceeds 0.5
    (K if it never does) — Fig. 9's convergence metric, read off the
    ``max_weight`` trajectory of :func:`run_eg_scan`."""
    hit = np.asarray(max_weight) > 0.5
    return int(np.argmax(hit)) + 1 if hit.any() else len(hit)
