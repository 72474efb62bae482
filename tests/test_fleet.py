"""Fleet-engine parity: core.fleet vs its two references.

``simulate_fleet`` must (a) collapse to the single-job pool simulator
bitwise when there is no contention — the per-job decision rules are the
very same jitted code — and (b) match the numpy ``MultiJobScheduler``
oracle through the demand-then-waterfall contention semantics at the
repo's python-vs-f32-device tolerance (1e-2 on utilities). On top of the
parity pins: capacity conservation, the least-slack-first grant order,
arrival/retirement masking, padded-job inertness, and the EG-weighted
admission helpers. The multi-device half mirrors tests/test_sharded_pool —
a subprocess forces 4 host devices and pins ``simulate_fleet_sharded``
bitwise against the unsharded engine across mesh shapes and padding cases.

One compiled program serves every policy mix: the kind is a mask over rows
in submission order, not a layout. ``data/fleet_exact_split.npz`` holds what
the kind-blocked engine it replaced (``[AHAP block | cheap block]`` with the
AHAP count static, commit 1ae6872) returned on the fleets of
:func:`_golden_inputs`; the masked program must return it bit for bit, on
one device and sharded over four.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")

from benchmarks.common import job_stream  # noqa: E402
from repro.configs.base import JobConfig, ThroughputConfig  # noqa: E402
from repro.core import fast_sim, fleet  # noqa: E402
from repro.core.market import vast_like_trace  # noqa: E402
from repro.core.multi_job import MultiJobScheduler  # noqa: E402
from repro.core.policy_pool import (  # noqa: E402
    KIND_AHAP,
    baseline_specs,
    paper_pool,
    rand_deadline_pool,
    specs_to_arrays,
)
from repro.core.predictor import NoisyPredictor  # noqa: E402

TPUT = ThroughputConfig(mu1=0.9, mu2=0.95)
D = 10
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "fleet_exact_split.npz")


def _small_pool():
    return (paper_pool(omegas=(2,), sigmas=(0.5,))
            + rand_deadline_pool((0.4,)) + baseline_specs())


def _market(T, seed=5, noise_seed=3):
    tr = vast_like_trace(seed=seed, days=2).window(0, T + 1)
    prices = tr.prices[:T].astype(np.float32)
    avail = tr.avail[:T].astype(np.int64)
    pred = NoisyPredictor(tr, "fixed_uniform", 0.2, seed=noise_seed).matrix(
        fast_sim.W1MAX - 1
    )[:T].astype(np.float32)
    return tr, prices, avail, pred


def _rows(arrs, idx):
    return {k: np.asarray(arrs[k])[idx]
            for k in ("kind", "omega", "v", "sigma", "rho", "cfrac")}


# ---------------------------------------------------------------------------
# single job: no contention -> bitwise the pool simulator
# ---------------------------------------------------------------------------

def test_single_job_bitwise_matches_pool_sim():
    pool = _small_pool()
    arrs = specs_to_arrays(pool)
    job = JobConfig(workload=40, deadline=D, n_min=1, n_max=10, value=80.0)
    _, prices, avail, pred = _market(D, seed=1, noise_seed=0)
    stacked1 = fast_sim.stack_jobs([job])
    base = fast_sim.simulate_pool_jobs(
        arrs, stacked1, TPUT, prices[None], avail[None], pred[None]
    )
    for li in range(len(pool)):
        out = fleet.simulate_fleet(
            _rows(arrs, [li]), stacked1, [0], TPUT, prices, avail, pred
        )
        for k in ("utility", "cost", "completion_time", "z_ddl", "completed",
                  "n_od", "n_spot"):
            np.testing.assert_array_equal(
                np.asarray(base[k])[0, li], np.asarray(out[k])[0],
                err_msg=f"{k} lane={pool[li].name}",
            )


# ---------------------------------------------------------------------------
# multi-job: the numpy oracle, conservation, padding, 1-device fallback
# ---------------------------------------------------------------------------

def _contended_fleet(J=12, T=24):
    pool = _small_pool()
    arrs = specs_to_arrays(pool)
    rng = np.random.default_rng(7)
    tr, prices, avail, pred = _market(T)
    jobs = list(job_stream(rng, J, deadline=D))
    arrivals = rng.integers(0, 8, size=J)
    idx = rng.integers(0, len(pool), size=J)
    rows = _rows(arrs, idx)
    out = fleet.simulate_fleet(rows, fast_sim.stack_jobs(jobs), arrivals,
                               TPUT, prices, avail, pred)
    return pool, idx, jobs, arrivals, tr, prices, avail, pred, rows, out


def test_multi_job_matches_numpy_oracle():
    (pool, idx, jobs, arrivals, tr, _, _, pred, _, out) = _contended_fleet()
    T = len(tr.prices) - 1
    sched = MultiJobScheduler(TPUT, tr)
    for i in range(len(jobs)):
        sched.submit(int(arrivals[i]), jobs[i], pool[int(idx[i])].build(),
                     pred=pred)
    res = {r.job_id: r for r in sched.run(T)}
    for i in range(len(jobs)):
        for field, key in (("utility", "utility"), ("cost", "cost"),
                           ("completion_time", "completion_time")):
            np.testing.assert_allclose(
                float(np.asarray(out[key])[i]), getattr(res[i], field),
                atol=1e-2, err_msg=f"job {i} ({pool[int(idx[i])].name}) {key}",
            )


def test_spot_grants_conserve_supply():
    (*_, avail, _, _, out) = _contended_fleet()
    granted = np.asarray(out["n_spot"]).sum(axis=0)
    assert np.all(granted <= avail), (granted, avail)


def test_padded_jobs_are_inert():
    (pool, idx, jobs, arrivals, tr, prices, avail, pred, rows, out) = \
        _contended_fleet()
    T = len(prices)
    J = len(jobs)
    jobs_p = jobs + [jobs[0]]
    rows_p = {k: np.concatenate([v, v[:1]]) for k, v in rows.items()}
    arr_p = np.concatenate([arrivals, [T]])  # arrival = T: never live
    out_p = fleet.simulate_fleet(rows_p, fast_sim.stack_jobs(jobs_p), arr_p,
                                 TPUT, prices, avail, pred)
    for k in out:
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(out_p[k])[:J], err_msg=k
        )


def test_sharded_single_device_fallback_bitwise():
    import jax

    assert jax.device_count() == 1
    (_, _, jobs, arrivals, _, prices, avail, pred, rows, out) = \
        _contended_fleet()
    sh = fleet.simulate_fleet_sharded(rows, fast_sim.stack_jobs(jobs),
                                      arrivals, TPUT, prices, avail, pred)
    for k in out:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(sh[k]),
                                      err_msg=k)


# ---------------------------------------------------------------------------
# one program whatever the mix, bitwise the kind-blocked engine it replaced
# ---------------------------------------------------------------------------

def _golden_inputs():
    """The fleets of the exact-split pin, by name: ``(rows, jobs,
    arrivals, prices, avail, pred, kwargs)``. Twelve contended jobs over
    24 slots with a mixed, an all-AHAP and an all-cheap policy draw (one
    shape, three AHAP counts), one job alone, and the mixed fleet on a
    storm-faulted market with the flight recorder and the armed
    prediction-failure monitor."""
    from repro.chaos import FallbackConfig, inject, storm_schedule

    pool = _small_pool()
    arrs = specs_to_arrays(pool)
    kind = np.asarray(arrs["kind"])
    ahap, cheap = (np.flatnonzero(kind == KIND_AHAP),
                   np.flatnonzero(kind != KIND_AHAP))
    J, T = 12, 24
    rng = np.random.default_rng(7)
    _, prices, avail, pred = _market(T)
    jobs = fast_sim.stack_jobs(list(job_stream(rng, J, deadline=D)))
    arrivals = rng.integers(0, 8, size=J)
    mixed = rng.integers(0, len(pool), size=J)
    market = (prices, avail, pred)
    cases = {
        "mixed": (mixed, jobs, arrivals, market, {}),
        "all_ahap": (rng.choice(ahap, J), jobs, arrivals, market, {}),
        "all_cheap": (rng.choice(cheap, J), jobs, arrivals, market, {}),
        "one_job": (ahap[1:2], fast_sim.slice_jobs(jobs, 0, 1),
                    np.array([2]), market, {}),
        "storm_collect_fallback": (
            mixed, jobs, arrivals,
            inject(prices, avail, pred, storm_schedule(
                1, T, n_storms=2, storm_len=5, pred_fault="stale")),
            {"collect": True,
             "fallback": FallbackConfig(threshold=0.5, lam=0.5)}),
    }
    return {name: (_rows(arrs, idx), j, a, *mk, kw)
            for name, (idx, j, a, mk, kw) in cases.items()}


@pytest.fixture(scope="module")
def golden():
    with np.load(GOLDEN) as g:
        return {k: g[k] for k in g.files}


def _assert_golden(out, golden, name):
    keys = sorted(k.split("/", 1)[1] for k in golden
                  if k.startswith(name + "/"))
    assert sorted(out) == keys, (name, sorted(out))
    for k in keys:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      golden[f"{name}/{k}"],
                                      err_msg=f"{name} {k}")


@pytest.mark.parametrize("name", ["mixed", "all_ahap", "all_cheap",
                                  "one_job", "storm_collect_fallback"])
def test_masked_layout_bitwise_matches_exact_split(name, golden):
    rows, jobs, arrivals, prices, avail, pred, kw = _golden_inputs()[name]
    out = fleet.simulate_fleet(rows, jobs, arrivals, TPUT, prices, avail,
                               pred, **kw)
    _assert_golden(out, golden, name)


def test_one_program_whatever_the_mix():
    """Three fleets of one shape with 0, some and all jobs on AHAP lanes,
    each twice: the fleet program compiles for the first call only, on
    the single-device and the sharded entry alike."""
    import jax

    cases = _golden_inputs()
    event = "/jax/core/compile/backend_compile_duration"
    n = [0]

    def listen(name, duration, **_):
        n[0] += name == event

    def run_all(sim):
        for name in ("all_cheap", "mixed", "all_ahap") * 2:
            rows, jobs, arrivals, prices, avail, pred, _ = cases[name]
            jax.block_until_ready(sim(rows, jobs, arrivals, TPUT, prices,
                                      avail, pred)["utility"])

    counts = set(int(np.sum(cases[c][0]["kind"] == KIND_AHAP))
                 for c in ("all_cheap", "mixed", "all_ahap"))
    assert len(counts) == 3, counts
    fleet._fleet_call.clear_cache()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for sim in (fleet.simulate_fleet, fleet.simulate_fleet_sharded):
            run_all(sim)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert n[0] == 1, n[0]


def test_thousandfold_pool_matches_numpy_oracle():
    """The benchmark's pool: the paper market's availability x 1000, far
    above the 16-unit unit table. The device engine still matches the
    numpy oracle, which decides with python policy objects."""
    from repro.core.market import Trace

    pool = _small_pool()
    arrs = specs_to_arrays(pool)
    J, T = 12, 24
    rng = np.random.default_rng(3)
    tr, prices, avail, pred = _market(T)
    big = Trace(tr.prices, tr.avail * 1000)
    avail_k = avail * 1000
    pred_k = pred.copy()
    pred_k[..., 1] *= 1000
    jobs = list(job_stream(rng, J, deadline=D))
    arrivals = rng.integers(0, 8, size=J)
    idx = rng.integers(0, len(pool), size=J)
    out = fleet.simulate_fleet(_rows(arrs, idx), fast_sim.stack_jobs(jobs),
                               arrivals, TPUT, prices, avail_k, pred_k)
    assert int(np.asarray(out["n_spot"]).sum()) > 0
    sched = MultiJobScheduler(TPUT, big)
    for i in range(J):
        sched.submit(int(arrivals[i]), jobs[i], pool[int(idx[i])].build(),
                     pred=pred_k)
    res = {r.job_id: r for r in sched.run(T)}
    for i in range(J):
        np.testing.assert_allclose(
            float(np.asarray(out["utility"])[i]), res[i].utility, atol=1e-2,
            err_msg=f"job {i} ({pool[int(idx[i])].name})")


# ---------------------------------------------------------------------------
# waterfall order + masking semantics, pinned on a hand-checkable scenario
# ---------------------------------------------------------------------------

def test_least_slack_first_and_completion_release():
    """Two all-spot (MSU) jobs against a constant 8-unit pool: the tight
    deadline drains first (6 of 8), the slack one rides the residual (2)
    until it completes at slot 2 — after which it stops demanding and the
    supply it held is NOT granted to anyone (sum drops), while the tight
    job keeps its full grant through its deadline and nothing is allocated
    outside either job's live window."""
    T = 8
    prices = np.full(T, 0.5, np.float32)
    avail = np.full(T, 8, np.int64)
    tight = JobConfig(workload=60, deadline=5, n_min=1, n_max=6, value=80.0)
    slackj = JobConfig(workload=4, deadline=10, n_min=1, n_max=6, value=80.0)
    from repro.core.policy_pool import KIND_MSU

    rows = {"kind": np.array([KIND_MSU, KIND_MSU])}
    out = fleet.simulate_fleet(rows, fast_sim.stack_jobs([tight, slackj]),
                               [0, 0], TPUT, prices, avail, None)
    ns = np.asarray(out["n_spot"])
    # tight job: full 6-unit grant on every live slot, nothing after d=5
    np.testing.assert_array_equal(ns[0], [6, 6, 6, 6, 6, 0, 0, 0])
    # slack job: residual 2 until it completes during slot 2, then retired
    np.testing.assert_array_equal(ns[1], [2, 2, 2, 0, 0, 0, 0, 0])
    assert bool(np.asarray(out["completed"])[1])
    # slot-2 progress: 1.8 (ramp-up mu1) + 2.0 + 2.0 covers workload 4
    np.testing.assert_allclose(float(np.asarray(out["completion_time"])[1]),
                               2.1, atol=1e-6)
    assert not bool(np.asarray(out["completed"])[0])


def test_arrival_masks_allocations():
    """A job arriving at t=a never holds capacity outside [a, a+d)."""
    (_, _, jobs, arrivals, _, _, _, _, _, out) = _contended_fleet()
    ns = np.asarray(out["n_spot"])
    no = np.asarray(out["n_od"])
    T = ns.shape[1]
    ts = np.arange(T)[None, :]
    a = np.asarray(arrivals)[:, None]
    d = np.asarray([j.deadline for j in jobs])[:, None]
    outside = (ts < a) | (ts >= a + d)
    assert not np.any(ns[outside]), "spot allocated outside live window"
    assert not np.any(no[outside]), "on-demand allocated outside live window"


# ---------------------------------------------------------------------------
# EG-weighted admission
# ---------------------------------------------------------------------------

def test_policy_rows_from_weights():
    import jax.numpy as jnp

    from repro.core import engine, selector

    pool = _small_pool()
    arrs = specs_to_arrays(pool)
    w = np.zeros(len(pool))
    w[3], w[5] = 2.0, 1.0

    rows, idx = fleet.policy_rows_from_weights(arrs, w, 8, greedy=True)
    assert np.all(idx == 3)
    for k in rows:
        np.testing.assert_array_equal(np.asarray(rows[k]),
                                      np.asarray(arrs[k])[idx], err_msg=k)

    rows2, idx2 = fleet.policy_rows_from_weights(
        arrs, w, 256, rng=np.random.default_rng(0)
    )
    assert set(np.unique(idx2)) <= {3, 5}
    assert 0.5 < float(np.mean(idx2 == 3)) < 0.85  # ~2/3 from the 2:1 weights
    for k in rows2:
        np.testing.assert_array_equal(np.asarray(rows2[k]),
                                      np.asarray(arrs[k])[idx2], err_msg=k)

    # rng=None must be deterministic (fixed seed), not time-dependent
    _, ia = fleet.policy_rows_from_weights(arrs, w, 16)
    _, ib = fleet.policy_rows_from_weights(arrs, w, 16)
    np.testing.assert_array_equal(ia, ib)

    # the engine-side hook delegates here with the selector's final weights
    st = selector.eg_init(len(pool), 16)._replace(
        weights=jnp.asarray(w / w.sum(), jnp.float32)
    )
    res = engine.SelectionResult(
        state=st, mean_utility=np.zeros(len(pool)),
        max_weight=np.zeros(1), regret=np.zeros(1), n_jobs=0,
    )
    _, idx3 = res.admission_rows(arrs, 8, greedy=True)
    np.testing.assert_array_equal(idx3, idx)


# ---------------------------------------------------------------------------
# sharded engine == unsharded engine, bitwise, on 4 forced host devices
# ---------------------------------------------------------------------------

# Job counts 3/5/9 exercise the under-, padding- and non-dividing layouts of
# the per-device row slices (pads last); the mesh list covers the
# default 1-D jobs mesh, the 2-D (jobs, lanes) mesh (fleet replicates over
# "lanes"), lanes-only (jobs axis size 1 -> unsharded fallback) and an
# explicit 1-D shape.
_CHILD = r"""
import numpy as np
import jax

assert jax.device_count() == 4, jax.devices()

from benchmarks.common import job_stream
from repro.configs.base import ThroughputConfig
from repro.core import fast_sim, fleet
from repro.core.market import vast_like_trace
from repro.core.policy_pool import (
    baseline_specs, paper_pool, rand_deadline_pool, specs_to_arrays,
)
from repro.core.predictor import NoisyPredictor
from repro.launch.mesh import make_pool_mesh

TPUT = ThroughputConfig(mu1=0.9, mu2=0.95)
d = 10
T = 20
pool = (paper_pool(omegas=(2,), sigmas=(0.5,))
        + rand_deadline_pool((0.4,)) + baseline_specs())
arrs = specs_to_arrays(pool)
tr = vast_like_trace(seed=5, days=1).window(0, T + 1)
prices = tr.prices[:T].astype(np.float32)
avail = tr.avail[:T].astype(np.int64)
pred = NoisyPredictor(tr, "fixed_uniform", 0.2, seed=3).matrix(
    fast_sim.W1MAX - 1)[:T].astype(np.float32)
rng = np.random.default_rng(11)
MESHES = [None, (2, 2), (1, 4), (4,)]
for J in (3, 5, 9):
    jobs = list(job_stream(rng, J, deadline=d))
    arrivals = rng.integers(0, 8, size=J)
    idx = rng.integers(0, len(pool), size=J)
    rows = {k: np.asarray(arrs[k])[idx] for k in
            ("kind", "omega", "v", "sigma", "rho", "cfrac")}
    stacked = fast_sim.stack_jobs(jobs)
    base = fleet.simulate_fleet(rows, stacked, arrivals, TPUT,
                                prices, avail, pred)
    for shape in MESHES:
        sh = fleet.simulate_fleet_sharded(
            rows, stacked, arrivals, TPUT, prices, avail, pred,
            mesh=None if shape is None else make_pool_mesh(shape=shape))
        for k in base:
            np.testing.assert_array_equal(
                np.asarray(base[k]), np.asarray(sh[k]),
                err_msg=f"{k} J={J} mesh={shape}")

# collect=True: the telemetry-carrying fleet (including the all-gathered
# waterfall rank series) shards bitwise too, and its shared keys match the
# collect=False run (one config — J=9 inputs left in scope by the loop)
tel = fleet.simulate_fleet(rows, stacked, arrivals, TPUT, prices, avail,
                           pred, collect=True)
tel_sh = fleet.simulate_fleet_sharded(
    rows, stacked, arrivals, TPUT, prices, avail, pred,
    mesh=make_pool_mesh(shape=(4,)), collect=True)
assert set(tel) == set(tel_sh) and len(tel) == len(base) + 12, sorted(tel)
for k in tel:
    np.testing.assert_array_equal(
        np.asarray(tel[k]), np.asarray(tel_sh[k]), err_msg=f"collect {k}")
for k in base:
    np.testing.assert_array_equal(
        np.asarray(base[k]), np.asarray(tel[k]),
        err_msg=f"collect-vs-base {k}")

# fallback: fallback=None rides the same compiled program as the default
# (bitwise vs base), and the ARMED prediction-failure monitor shards
# bitwise too — on storm-faulted inputs that actually trigger it
# (collect + fallback adds the 12 fleet keys + 2 fallback keys)
from repro.chaos import FallbackConfig, inject, storm_schedule
none = fleet.simulate_fleet(rows, stacked, arrivals, TPUT, prices, avail,
                            pred, fallback=None)
for k in base:
    np.testing.assert_array_equal(
        np.asarray(base[k]), np.asarray(none[k]), err_msg=f"fb-none {k}")
pf, af, prf = inject(prices, avail, pred,
                     storm_schedule(1, T, n_storms=2, storm_len=5,
                                    pred_fault="stale"))
cfg = FallbackConfig(threshold=0.5, lam=0.5)
fb = fleet.simulate_fleet(rows, stacked, arrivals, TPUT, pf, af, prf,
                          collect=True, fallback=cfg)
assert len(fb) == len(base) + 14, sorted(fb)
assert np.asarray(fb["tel_fallback"]).any(), "monitor never armed"
for shape in MESHES:
    fb_sh = fleet.simulate_fleet_sharded(
        rows, stacked, arrivals, TPUT, pf, af, prf,
        mesh=None if shape is None else make_pool_mesh(shape=shape),
        collect=True, fallback=cfg)
    assert set(fb_sh) == set(fb)
    for k in fb:
        np.testing.assert_array_equal(
            np.asarray(fb[k]), np.asarray(fb_sh[k]),
            err_msg=f"fleet fallback {k} mesh={shape}")

# one sharded program whatever the mix: after the first fleet of a shape,
# fleets with other AHAP counts compile nothing; and on every mesh the
# results are bitwise those of the kind-blocked engine this one replaced
import importlib.util
import os
spec = importlib.util.spec_from_file_location(
    "test_fleet_inputs", os.environ["FLEET_TEST_MODULE"])
tf = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tf)
gold = dict(np.load(tf.GOLDEN))
cases = tf._golden_inputs()
mesh4 = make_pool_mesh(shape=(4,))
compiles = [0]

def listen(name, duration, **_):
    compiles[0] += name == "/jax/core/compile/backend_compile_duration"

def sharded(name, mesh):
    r, j, a, p, av, pr, kw = cases[name]
    return fleet.simulate_fleet_sharded(r, j, a, tf.TPUT, p, av, pr,
                                        mesh=mesh, **kw)

jax.block_until_ready(sharded("all_cheap", mesh4)["utility"])
jax.monitoring.register_event_duration_secs_listener(listen)
for name in ("mixed", "all_ahap", "all_cheap"):
    jax.block_until_ready(sharded(name, mesh4)["utility"])
jax.monitoring.unregister_event_duration_listener(listen)
assert compiles[0] == 0, compiles[0]
for name in cases:
    for shape in MESHES:
        tf._assert_golden(
            sharded(name, None if shape is None
                    else make_pool_mesh(shape=shape)), gold, name)
print("FLEET-SHARDED-OK")
"""


def test_fleet_sharded_matches_unsharded_4dev_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["FLEET_TEST_MODULE"] = os.path.abspath(__file__)
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, os.path.dirname(SRC)] + env.get("PYTHONPATH", "").split(os.pathsep)
    ).rstrip(os.pathsep)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "FLEET-SHARDED-OK" in out.stdout
