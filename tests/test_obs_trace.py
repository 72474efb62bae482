"""The selection path's profiler spans and transfer/launch counters, under a
real ``jax.profiler`` capture on the CPU.

* every ``sched.*`` span is recorded, the engine's inside ``sched.select``
  and the prep's inside ``sched.prep``;
* one call's counter increases equal what the inputs' ``nbytes``, the
  pool's two kind partitions and the forecast rows imply, and ``launches``
  equals the programs the trace saw run;
* counters and results are the same with and without a capture;
* the fleet engine's ``sched.fleet.*`` spans are recorded, and one fleet
  call's counters equal what its rows, arrivals and market imply.
"""
import glob

import jax
import numpy as np
import pytest

from benchmarks.common import PAPER_TPUT, job_stream_arrays, paper_market
from repro.core import engine, fast_sim, selector
from repro.core.policy_pool import (
    KIND_AHAP,
    baseline_specs,
    paper_pool,
    rand_deadline_pool,
    specs_to_arrays,
)
from repro.core.predictor import BATCHED_SEEDING_MIN_ROWS
from repro.obs import counters

ENGINE_SPANS = ("sched.stage", "sched.pool.lanes", "sched.pool.ahap",
                "sched.pool.cheap", "sched.pool.merge", "sched.eg",
                "sched.fetch")
PREP_SPANS = ("sched.prep.windows", "sched.prep.forecast")
FLEET_SPANS = ("sched.fleet.prep", "sched.fleet.scan", "sched.fleet.gather")


@pytest.fixture(scope="module")
def setup():
    pool = (paper_pool(omegas=(1, 3), sigmas=(0.3, 0.7))
            + rand_deadline_pool((0.3,)) + baseline_specs())
    arrs = specs_to_arrays(pool)
    rng = np.random.default_rng(5)
    n = 6
    trace = paper_market(seed=21, days=4)
    jobs = job_stream_arrays(rng, n)
    d = int(np.asarray(jobs.deadline)[0])
    t0s = rng.integers(0, len(trace) - d - 1, size=n)
    seeds = 5 * 100003 + np.arange(n)
    state = selector.eg_init(len(pool), n)

    def call():
        prices, avail, preds = engine.prepare_noisy_inputs(
            trace, t0s, d, "fixed_uniform", 0.2, seeds)
        res = engine.simulate_and_select(
            arrs, jobs, PAPER_TPUT, prices, avail, preds, state=state,
            return_utilities=True)
        jax.block_until_ready(res.state)
        return res, (prices, avail, preds)

    call()                                   # compile outside the counts
    return arrs, jobs, call


def _counted(call):
    c0 = counters.snapshot()
    out = call()
    c1 = counters.snapshot()
    return out, {k: v - c0.get(k, 0) for k, v in c1.items()}


def _capture(call, d):
    """``call`` counted under a profiler capture written to ``d``, and the
    capture's sched.* host spans and program runs."""
    jax.profiler.start_trace(d)
    try:
        traced = _counted(call)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    spans, runs = {}, set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("sched."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
                stats = {k: v for k, v in e.stats}
                if "hlo_module" in stats and "run_id" in stats:
                    runs.add((stats["hlo_module"], stats["run_id"]))
    return traced, spans, runs


@pytest.fixture(scope="module")
def captured(setup, tmp_path_factory):
    """One call without a capture, then one under a capture, and what the
    capture recorded: the sched.* host spans and the program runs."""
    _, _, call = setup
    plain = _counted(call)
    traced, spans, runs = _capture(call,
                                   str(tmp_path_factory.mktemp("trace")))
    return plain, traced, spans, runs


def _inside(inner, outer):
    return all(any(a <= s and e <= b for a, b in outer) for s, e in inner)


def test_every_span_recorded_and_nested(captured):
    _, _, spans, _ = captured
    for name in ("sched.select", "sched.prep") + ENGINE_SPANS + PREP_SPANS:
        assert spans.get(name), name
    assert len(spans["sched.select"]) == len(spans["sched.prep"]) == 1
    for name in ENGINE_SPANS:
        assert _inside(spans[name], spans["sched.select"]), name
    for name in PREP_SPANS:
        assert _inside(spans[name], spans["sched.prep"]), name
    assert not _inside(spans["sched.prep"], spans["sched.select"])


def test_counters_match_the_inputs(setup, captured):
    arrs, jobs, _ = setup
    (res, (prices, avail, preds)), delta = captured[0]
    kind = np.asarray(arrs["kind"])
    ahap, cheap = kind == KIND_AHAP, kind != KIND_AHAP
    n_pol = kind.size
    assert ahap.any() and cheap.any()        # two kind partitions
    f32 = lambda mask: 4 * int(mask.sum())
    lanes = (arrs["omega"][ahap].nbytes + arrs["v"][ahap].nbytes
             + arrs["sigma"][ahap].nbytes + f32(ahap)            # rho
             + arrs["kind"][cheap].nbytes + arrs["sigma"][cheap].nbytes
             + f32(cheap))                                       # cfrac
    job_bytes = sum(np.asarray(f).nbytes for f in jobs)
    want = {
        # 3 staged inputs, 4 AHAP + 3 cheap lane arrays, the job leaves
        # into both pool programs and the EG program (the scatter-merge's
        # pool order is a constant of its program: nothing is sent)
        "h2d_arrays": 3 + 7 + 3 * len(jobs),
        "h2d_bytes": (prices.nbytes + avail.nbytes + preds.nbytes + lanes
                      + 3 * job_bytes),
        # u_sum's zeros (2), both pool programs, the one scatter-merge
        # program, the column sum and the add, the EG program
        "launches": 2 + 2 + 1 + 2 + 1,
        # u_sum, max_weight, regret, utilities
        "d2h_arrays": 4,
        "select_calls": 1,
        "d2h_bytes": 4 * n_pol + res.max_weight.nbytes + res.regret.nbytes
        + res.utilities.nbytes,
        # the prep's forecast rows, and those seeded in one batched pass
        "forecast_rows": len(prices),
        "forecast_rows_batched": (
            len(prices) if len(prices) >= BATCHED_SEEDING_MIN_ROWS else 0),
    }
    # counters another module's calls created earlier in this process
    # (the fleet's) are in the delta too, increased by nothing
    moved = lambda d: {k: v for k, v in d.items() if v}
    assert moved(delta) == moved(want)


def test_launches_are_the_programs_run(captured):
    _, (_, delta), _, runs = captured
    assert delta["launches"] == len(runs)


def test_capture_changes_nothing(captured):
    (res0, _), delta0 = captured[0]
    (res1, _), delta1 = captured[1]
    assert delta0 == delta1
    np.testing.assert_array_equal(res0.utilities, res1.utilities)
    np.testing.assert_array_equal(res0.regret, res1.regret)
    np.testing.assert_array_equal(np.asarray(res0.state.weights),
                                  np.asarray(res1.state.weights))


def test_sharded_runners_named_apart():
    """Each kind's sharded partition runner — on a flat or a regional
    market, with or without per-region on-demand prices — and the sharded
    fleet's carry a name of their own, which a device trace shows as
    ``jit_<name>``."""
    from jax.sharding import PartitionSpec

    from repro.core import fleet
    from repro.launch.mesh import make_pool_mesh

    mesh = make_pool_mesh(jax.devices()[:1])
    spec = PartitionSpec()
    names = {
        fast_sim._sharded_pool_call(mesh, PAPER_TPUT, "xla", delta_mig,
                                    ahap, spec, spec, spec,
                                    has_p_od=pod).__name__
        for ahap in (True, False) for delta_mig, pod in (
            (None, False), (1, False), (1, True))}
    assert names == {"sharded_pool_ahap", "sharded_pool_cheap"}
    assert fleet._sharded_fleet_call(
        mesh, PAPER_TPUT, "xla").__name__ == "sharded_fleet_scan"


# ---------------------------------------------------------------------------
# the fleet engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_captured(tmp_path_factory):
    """Ten contending jobs with a mixed policy draw over 16 slots: one
    call to compile, then one counted under a capture."""
    from repro.core import fleet

    pool = (paper_pool(omegas=(1, 3), sigmas=(0.3, 0.7))
            + rand_deadline_pool((0.3,)) + baseline_specs())
    arrs = specs_to_arrays(pool)
    rng = np.random.default_rng(9)
    n, slots = 10, 16
    jobs = job_stream_arrays(rng, n)
    arrivals = rng.integers(0, 8, size=n).astype(np.int32)
    idx = rng.integers(0, len(pool), size=n)
    rows = {k: np.asarray(arrs[k])[idx] for k in fleet._POLICY_KEYS}
    trace = paper_market(seed=21, days=2)
    prices = trace.prices[:slots].astype(np.float32)
    avail = trace.avail[:slots].astype(np.int64)

    def call():
        out = fleet.simulate_fleet(rows, jobs, arrivals, PAPER_TPUT, prices,
                                   avail)
        jax.block_until_ready(out)
        return out

    call()
    traced, spans, runs = _capture(call,
                                   str(tmp_path_factory.mktemp("fleet")))
    return (rows, jobs, arrivals, prices, avail), traced, spans, runs


def test_fleet_spans_recorded_in_order(fleet_captured):
    _, _, spans, _ = fleet_captured
    for name in FLEET_SPANS:
        assert len(spans.get(name, [])) == 1, name
    (prep,), (scan,), (gather,) = (spans[n] for n in FLEET_SPANS)
    assert prep[1] <= scan[0] and scan[1] <= gather[0]


def test_fleet_counters_match_the_inputs(fleet_captured):
    (rows, jobs, arrivals, prices, avail), (_, delta), _, runs = \
        fleet_captured
    n, slots = len(arrivals), len(prices)
    d = np.asarray(jobs.deadline)
    n_ahap = int(np.sum(rows["kind"] == KIND_AHAP))
    assert 0 < n_ahap < n                    # a mixed draw
    host = list(rows.values()) + list(jobs) + [arrivals, prices, avail]
    want = {
        "fleet_calls": 1,
        # the AHAP rule runs over every row; the other kinds' are masked
        "fleet_rows": n,
        "fleet_rows_padded": n - n_ahap,
        "fleet_cells": n * slots,
        "fleet_cells_live": int(np.sum(np.minimum(arrivals + d, slots)
                                       - arrivals)),
        # one program; its numpy arguments, the persistence forecast too
        "launches": 1,
        "h2d_arrays": len(host) + 1,
        "h2d_bytes": sum(x.nbytes for x in host) + slots * 6 * 2 * 4,
    }
    assert {k: v for k, v in delta.items() if v} == want
    assert delta["launches"] == len(runs)
