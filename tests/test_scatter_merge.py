"""The scatter-merge that puts the kind partitions' results back in pool
order (``fast_sim._scatter_merge``): one compiled program whose pool
permutation is a constant of the program.

* on every pool entry point, with the telemetry keys, on a pool that
  interleaves AHAP and cheap lanes, and on the one-partition pass-through,
  the merged dict equals, bit for bit, the eager per-key concatenate and
  gather it replaced (kept here as the reference);
* a second call with the same pool and shapes compiles nothing;
* a merge is one launch and hands the device no host array.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import JobConfig, ThroughputConfig
from repro.core import fast_sim
from repro.core.market import vast_like_trace
from repro.core.policy_pool import (
    KIND_AHAP,
    baseline_specs,
    paper_pool,
    rand_deadline_pool,
    region_pool,
    specs_to_arrays,
)
from repro.core.predictor import NoisyPredictor, RegionalPredictor
from repro.core.region_market import vast_like_regions
from repro.obs import counters

JOBS = (JobConfig(workload=80, deadline=10, n_min=1, n_max=12, value=120.0),
        JobConfig(workload=100, deadline=10, n_min=2, n_max=14, value=120.0))
TPUT = ThroughputConfig(mu1=0.9, mu2=0.95)


def _eager_merge(parts, index_arrays, axis):
    """The merge as it was: per key, an eager concatenate and an eager
    gather by the host permutation."""
    order = np.argsort(np.concatenate(index_arrays), kind="stable")
    return {
        k: jnp.take(jnp.concatenate([p[k] for p in parts], axis=axis), order,
                    axis=axis)
        for k in parts[0]
    }


def _mixed():
    return (paper_pool(omegas=(1, 3), sigmas=(0.3, 0.7))
            + rand_deadline_pool((0.2, 0.6)) + baseline_specs())


def _interleaved():
    pool = _mixed()
    return [pool[i] for i in np.random.default_rng(3).permutation(len(pool))]


POOLS = {
    "mixed": _mixed,
    "interleaved": _interleaved,
    "all_ahap": lambda: paper_pool(omegas=(1, 3), sigmas=(0.3, 0.7)),
    "all_cheap": lambda: rand_deadline_pool((0.2, 0.6)) + baseline_specs(),
    "regions": region_pool,
}


def _single_region_inputs():
    rows = []
    for seed in range(len(JOBS)):
        tr = vast_like_trace(seed=30 + seed, days=1).window(0, 10)
        pred = NoisyPredictor(tr, "fixed_uniform", 0.2, seed=seed).matrix(
            fast_sim.W1MAX - 1)
        rows.append(fast_sim.prepare_inputs(tr, pred, JOBS[0].deadline))
    return [np.stack([np.asarray(r[i]) for r in rows]) for i in range(3)]


def _region_inputs():
    mkt = vast_like_regions(3, seed=5, days=1).window(0, 11)
    rpred = RegionalPredictor(
        mkt, lambda t, r: NoisyPredictor(t, "fixed_uniform", 0.2, seed=r)
    ).matrix(fast_sim.W1MAX - 1)
    rp, ra, rpm = fast_sim.prepare_inputs(mkt, rpred, JOBS[0].deadline)
    return [np.stack([np.asarray(x)] * len(JOBS)) for x in (rp, ra, rpm)]


def _call(entry, pool, collect=False):
    """A closure running ``entry`` on ``pool``, and the lane axis the merge
    runs on (one job's call merges its one-row batch)."""
    arrs = specs_to_arrays(POOLS[pool]())
    jobs = fast_sim.stack_jobs(JOBS)
    if entry == "pool":
        prices, avail, pred = (x[0] for x in _single_region_inputs())
        j = fast_sim.JobArrays.of(JOBS[0])
        return (lambda: fast_sim.simulate_pool(
            arrs, j, TPUT, prices, avail, pred, collect=collect)), 1
    if entry == "jobs":
        prices, avail, pred = _single_region_inputs()
        return (lambda: fast_sim.simulate_pool_jobs(
            arrs, jobs, TPUT, prices, avail, pred, collect=collect)), 1
    prices, avail, pred = _region_inputs()
    return (lambda: fast_sim.simulate_pool_jobs(
        arrs, jobs, TPUT, prices, avail, pred, delta_mig=1,
        collect=collect)), 1


@pytest.fixture
def recorded(monkeypatch):
    """Every scatter-merge call of the test: its partitions, their pool
    indices, the axis, what it returned and the counters it moved."""
    calls = []
    merge = fast_sim._scatter_merge

    def record(parts, index_arrays, axis):
        c0 = counters.snapshot()
        out = merge(parts, index_arrays, axis)
        c1 = counters.snapshot()
        delta = {k: v - c0.get(k, 0) for k, v in c1.items()}
        calls.append((parts, index_arrays, axis, out, delta))
        return out

    monkeypatch.setattr(fast_sim, "_scatter_merge", record)
    return calls


CASES = [
    ("pool", "mixed", False),
    ("jobs", "mixed", False),
    ("regions", "regions", False),
    ("jobs", "mixed", True),
    ("regions", "regions", True),
    ("pool", "interleaved", False),
    ("jobs", "interleaved", False),
    ("jobs", "all_ahap", False),
    ("jobs", "all_cheap", False),
]


@pytest.mark.parametrize("entry,pool,collect", CASES,
                         ids=["-".join(map(str, c)) for c in CASES])
def test_merge_is_the_eager_merge_bitwise(recorded, entry, pool, collect):
    call, axis = _call(entry, pool, collect)
    result = call()
    ((parts, idxs, got_axis, out, _),) = recorded
    assert got_axis == axis
    if entry == "pool":
        # one job's result is the merged one-row batch's row
        assert list(result) == list(out)
        for k in out:
            np.testing.assert_array_equal(np.asarray(result[k]),
                                          np.asarray(out[k][0]), err_msg=k)
    else:
        assert out is result
    kind = np.asarray(specs_to_arrays(POOLS[pool]())["kind"])
    assert len(parts) == int((kind == KIND_AHAP).any()) + int(
        (kind != KIND_AHAP).any())
    if len(parts) == 1:
        assert out is parts[0]
    if collect:
        assert any(k.startswith("tel_") for k in out)
    want = _eager_merge(parts, idxs, axis)
    assert list(out) == list(want)
    for k in want:
        assert out[k].dtype == want[k].dtype, k
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(want[k]),
                                      err_msg=k)


@pytest.mark.parametrize("entry", ["pool", "jobs", "regions"])
def test_second_call_compiles_nothing(entry):
    call, _ = _call(entry, "interleaved" if entry != "regions" else "regions")
    first = call()
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    second = call()
    jax.block_until_ready(second)
    n = len(compiles)
    for k in first:
        np.testing.assert_array_equal(np.asarray(first[k]),
                                      np.asarray(second[k]), err_msg=k)
    assert n == 0


@pytest.mark.parametrize("pool", ["mixed", "interleaved"])
def test_merge_is_one_launch_and_sends_nothing(recorded, pool):
    call, _ = _call("jobs", pool)
    call()
    call()
    assert len(recorded) == 2
    for *_, delta in recorded:
        assert delta.get("launches", 0) == 1
        assert delta.get("h2d_arrays", 0) == 0
        assert delta.get("h2d_bytes", 0) == 0
