"""Batched seeding of the forecast stack's noise generators.

``predictor.seed_sequence_state`` / ``pcg64_states`` reproduce numpy's
``SeedSequence`` and ``PCG64`` seeding over a whole seed array, and
``noisy_matrix_batch`` draws bitwise what a per-row ``default_rng`` loop
draws, on both sides of ``BATCHED_SEEDING_MIN_ROWS``; the counters say
which path the rows took.
"""
import numpy as np
import pytest

from _hyp_compat import given, settings, st
from repro.core import fast_sim
from repro.core.market import gather_windows, vast_like_trace
from repro.core.predictor import (
    BATCHED_SEEDING_MIN_ROWS,
    NOISE_KINDS,
    NoisyPredictor,
    noisy_matrix_batch,
    pcg64_states,
    seed_sequence_state,
)
from repro.obs import counters

EDGE_SEEDS = [0, 1, 2**32 - 1, 2**32, 2**62 - 1, 2**63, 2**64 - 1]


def _numpy_words(s: int) -> np.ndarray:
    return np.random.SeedSequence(s).generate_state(4, np.uint64)


def _numpy_pcg64(s: int) -> tuple:
    st_ = np.random.PCG64(s).state["state"]
    return st_["state"], st_["inc"]


def _assert_seeding(seeds, values):
    got = seed_sequence_state(seeds)
    assert got.dtype == np.uint64 and got.shape == (len(values), 4)
    np.testing.assert_array_equal(
        got, np.stack([_numpy_words(s) for s in values]))
    assert pcg64_states(seeds) == [_numpy_pcg64(s) for s in values]


@pytest.mark.parametrize("seed", EDGE_SEEDS)
def test_seeding_matches_numpy_at_edges(seed):
    _assert_seeding(np.array([seed], np.uint64), [seed])


@pytest.mark.parametrize("dtype", [np.int64, np.uint64, np.int32, np.uint32])
def test_seeding_matches_numpy_for_integer_arrays(dtype):
    info = np.iinfo(dtype)
    values = [s for s in EDGE_SEEDS if s <= info.max]
    _assert_seeding(np.array(values, dtype), values)


@settings(max_examples=25)
@given(seed=st.integers(0, 2**31), n=st.integers(1, 48),
       bits=st.sampled_from([1, 8, 32, 33, 62, 63, 64]))
def test_seeding_matches_numpy_property(seed, n, bits):
    seeds = np.random.default_rng(seed).integers(0, 2**bits, n,
                                                 dtype=np.uint64)
    _assert_seeding(seeds, [int(s) for s in seeds])


def test_negative_seed_raises_as_default_rng():
    with pytest.raises(ValueError, match="expected non-negative integer"):
        np.random.default_rng(-1)
    with pytest.raises(ValueError, match="expected non-negative integer"):
        seed_sequence_state(np.array([3, -1], np.int64))
    with pytest.raises(ValueError, match="expected non-negative integer"):
        noisy_matrix_batch(np.ones((BATCHED_SEEDING_MIN_ROWS, 6)),
                           np.ones((BATCHED_SEEDING_MIN_ROWS, 6)),
                           "fixed_uniform", 0.1,
                           -np.arange(BATCHED_SEEDING_MIN_ROWS), 5)


def _stack_and_loop(kind, level, n_rows, seeds=None):
    """``noisy_matrix_batch`` over ``n_rows`` job windows, and the per-row
    ``default_rng`` loop it replaces (one ``NoisyPredictor`` per job)."""
    tr = vast_like_trace(seed=9, days=2)
    t0s = np.random.default_rng(3).integers(0, len(tr) - 12, n_rows)
    if seeds is None:
        seeds = np.random.default_rng(4).integers(0, 2**62, n_rows)
    levels = np.broadcast_to(np.asarray(level, float), (n_rows,))
    pw, aw = gather_windows(tr, t0s, 11)
    batch = noisy_matrix_batch(pw, aw, kind, level, seeds,
                               fast_sim.W1MAX - 1)
    loop = np.stack([
        NoisyPredictor(tr.window(int(t0), 11), kind, float(lv),
                       seed=int(s)).matrix(fast_sim.W1MAX - 1)
        for t0, s, lv in zip(t0s, seeds, levels)
    ])
    return batch, loop


@pytest.mark.parametrize("n_rows", [BATCHED_SEEDING_MIN_ROWS - 1,
                                    BATCHED_SEEDING_MIN_ROWS, 64])
@pytest.mark.parametrize("per_row_level", [False, True])
@pytest.mark.parametrize("kind", NOISE_KINDS)
def test_noisy_matrix_batch_bitwise_either_side(kind, per_row_level, n_rows):
    level = (np.linspace(0.0, 0.4, n_rows) if per_row_level else 0.3)
    batch, loop = _stack_and_loop(kind, level, n_rows)
    np.testing.assert_array_equal(batch, loop)


@pytest.mark.parametrize("n_rows", [1, BATCHED_SEEDING_MIN_ROWS - 1,
                                    BATCHED_SEEDING_MIN_ROWS, 40])
def test_counters_tell_the_path(n_rows):
    before = counters.snapshot()
    _stack_and_loop("fixed_uniform", 0.1, n_rows)
    after = counters.snapshot()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("forecast_rows", "forecast_rows_batched")}
    batched = n_rows if n_rows >= BATCHED_SEEDING_MIN_ROWS else 0
    assert delta == {"forecast_rows": n_rows,
                     "forecast_rows_batched": batched}


def test_seeds_beyond_64_bits_take_the_per_row_path():
    """An object array of Python ints (here past 2**64) is not an integer
    array: its rows are seeded one by one, as ``default_rng`` seeds them."""
    n = BATCHED_SEEDING_MIN_ROWS + 2
    seeds = np.array([2**70 + 5 * k for k in range(n)], dtype=object)
    before = counters.snapshot().get("forecast_rows_batched", 0)
    batch, loop = _stack_and_loop("magdep_uniform", 0.2, n, seeds=seeds)
    np.testing.assert_array_equal(batch, loop)
    assert counters.snapshot().get("forecast_rows_batched", 0) == before
