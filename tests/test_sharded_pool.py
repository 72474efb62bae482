"""Sharded-vs-single-device parity for the policy-pool simulator.

``simulate_pool_jobs_sharded`` on flat and on region-axis markets must be
BITWISE-equal to ``simulate_pool_jobs`` — per-(job, lane) cells are
independent and every op is elementwise over both grid axes, so laying the
grid over a device mesh (jobs-only 1-D, lanes-only, or the 2-D
(jobs, lanes) mesh) may not change a single bit. The multi-device half runs
in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(conftest forbids the forcing flag in the main test process), covering job
counts that divide the mesh, need padding, and undershoot the device count,
and lane partitions (15 AHAP / 9 cheap) that pad on every lane-axis layout.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")

# Runs inside the forced-4-device subprocess. Lane counts (15 AHAP + 3
# AHANP + 3 RAND + 3 baselines = 24 lanes, partitions 15/9) exercise the
# kind partition AND lane-axis padding on both the (1, 4) and (2, 2)
# meshes (15 % 4 = 3, 9 % 4 = 1, 15 % 2 = 1, 9 % 2 = 1); job counts 1/3/5
# exercise the under-, non-dividing- and padding paths of the jobs axes.
_CHILD = r"""
import numpy as np
import jax

assert jax.device_count() == 4, jax.devices()

from benchmarks.common import job_stream
from repro.configs.base import ThroughputConfig
from repro.core import fast_sim
from repro.core.market import vast_like_trace
from repro.core.policy_pool import (
    baseline_specs, paper_pool, rand_deadline_pool, region_pool,
    specs_to_arrays,
)
from repro.core.predictor import NoisyPredictor, RegionalPredictor
from repro.core.region_market import vast_like_regions
from repro.launch.mesh import make_pool_mesh

TPUT = ThroughputConfig(mu1=0.9, mu2=0.95)
pool = (paper_pool(omegas=(2, 3), sigmas=(0.3, 0.7, 0.9))
        + rand_deadline_pool((0.25, 0.5, 0.75)) + baseline_specs())
arrs = specs_to_arrays(pool)
n_ahap = int((arrs["kind"] == 0).sum())
assert n_ahap % 4 and (len(pool) - n_ahap) % 4, (n_ahap, len(pool))
rng = np.random.default_rng(0)
d = 10
MESHES = [None, (1, 4), (2, 2)]  # default 1-D jobs, lanes-only, 2-D
for n_jobs in (1, 3, 5):
    jobs = list(job_stream(rng, n_jobs, deadline=d))
    traces = [vast_like_trace(seed=40 + i, days=1).window(0, d + 1)
              for i in range(n_jobs)]
    prices = np.stack([t.prices[:d] for t in traces]).astype(np.float32)
    avail = np.stack([t.avail[:d] for t in traces]).astype(np.int64)
    preds = np.stack([
        NoisyPredictor(t, "fixed_uniform", 0.2, seed=i).matrix(
            fast_sim.W1MAX - 1
        )[:d]
        for i, t in enumerate(traces)
    ]).astype(np.float32)
    stacked = fast_sim.stack_jobs(jobs)
    base = fast_sim.simulate_pool_jobs(arrs, stacked, TPUT, prices, avail, preds)
    for shape in MESHES:
        sh = fast_sim.simulate_pool_jobs_sharded(
            arrs, stacked, TPUT, prices, avail, preds,
            mesh=None if shape is None else make_pool_mesh(shape=shape),
        )
        for k in base:
            np.testing.assert_array_equal(
                np.asarray(base[k]), np.asarray(sh[k]),
                err_msg=f"{k} n_jobs={n_jobs} mesh={shape}",
            )

# collect=True: the telemetry-carrying program shards bitwise too, and its
# shared keys match the collect=False run (one config bounds the runtime;
# the loop leaves n_jobs=5 inputs in scope)
tel = fast_sim.simulate_pool_jobs(
    arrs, stacked, TPUT, prices, avail, preds, collect=True)
tel_sh = fast_sim.simulate_pool_jobs_sharded(
    arrs, stacked, TPUT, prices, avail, preds,
    mesh=make_pool_mesh(shape=(2, 2)), collect=True)
assert set(tel) == set(tel_sh) and len(tel) == len(base) + 7, sorted(tel)
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
# (collect + fallback adds 7 slot keys + 2 fallback keys)
from repro.chaos import FallbackConfig, inject, storm_schedule
none = fast_sim.simulate_pool_jobs(
    arrs, stacked, TPUT, prices, avail, preds, fallback=None)
for k in base:
    np.testing.assert_array_equal(
        np.asarray(base[k]), np.asarray(none[k]), err_msg=f"fb-none {k}")
pf, af, prf = inject(prices, avail, preds,
                     storm_schedule(1, d, n_storms=2, storm_len=4,
                                    spike_mag=2.5, pred_fault="stale"))
cfg = FallbackConfig(threshold=0.5, lam=0.5)
fb = fast_sim.simulate_pool_jobs(
    arrs, stacked, TPUT, pf, af, prf, collect=True, fallback=cfg)
assert len(fb) == len(base) + 9, sorted(fb)
assert np.asarray(fb["tel_fallback"]).any(), "monitor never armed"
for shape in MESHES:
    fb_sh = fast_sim.simulate_pool_jobs_sharded(
        arrs, stacked, TPUT, pf, af, prf,
        mesh=None if shape is None else make_pool_mesh(shape=shape),
        collect=True, fallback=cfg)
    assert set(fb_sh) == set(fb)
    for k in fb:
        np.testing.assert_array_equal(
            np.asarray(fb[k]), np.asarray(fb_sh[k]),
            err_msg=f"fallback {k} mesh={shape}")

# multi-region: same meshes over the (J, R, T) market tensors
mkt = vast_like_regions(3, seed=1, days=1)
rarrs = specs_to_arrays(region_pool())
jobs = list(job_stream(rng, 3, deadline=d))
wins = [mkt.window(i * 4, d + 1) for i in range(3)]
rp = np.stack([w.prices[:, :d] for w in wins]).astype(np.float32)
ra = np.stack([w.avail[:, :d] for w in wins]).astype(np.int64)
rpm = np.stack([
    RegionalPredictor(
        w, lambda t, r: NoisyPredictor(t, "fixed_uniform", 0.2, seed=r)
    ).matrix(fast_sim.W1MAX - 1)[:, :d]
    for w in wins
]).astype(np.float32)
stacked = fast_sim.stack_jobs(jobs)
rbase = fast_sim.simulate_pool_jobs(
    rarrs, stacked, TPUT, rp, ra, rpm, delta_mig=1)
for shape in MESHES:
    sh = fast_sim.simulate_pool_jobs_sharded(
        rarrs, stacked, TPUT, rp, ra, rpm, delta_mig=1,
        mesh=None if shape is None else make_pool_mesh(shape=shape),
    )
    for k in rbase:
        np.testing.assert_array_equal(
            np.asarray(rbase[k]), np.asarray(sh[k]),
            err_msg=f"{k} regions mesh={shape}",
        )

# region engine knobs: collect=True (+9 keys: 7 slot + 2 migration
# series), the armed fallback monitor (+2 more), and per-region od
# multipliers — all three shard bitwise on every mesh layout, and the
# collect run's shared keys match the plain region run
p_od = np.array([1.0, 1.5, 0.7], np.float32)
rfull = fast_sim.simulate_pool_jobs(
    rarrs, stacked, TPUT, rp, ra, rpm, delta_mig=1,
    collect=True, fallback=FallbackConfig(threshold=0.5, lam=0.5),
    p_od=p_od)
rtel = fast_sim.simulate_pool_jobs(
    rarrs, stacked, TPUT, rp, ra, rpm, delta_mig=1, collect=True)
assert len(rtel) == len(rbase) + 9, sorted(rtel)
assert len(rfull) == len(rbase) + 11, sorted(rfull)
for k in rbase:
    np.testing.assert_array_equal(
        np.asarray(rbase[k]), np.asarray(rtel[k]),
        err_msg=f"region collect-vs-base {k}")
np.testing.assert_array_equal(
    np.asarray(rtel["tel_migration"]).sum(axis=-1),
    np.asarray(rtel["migrations"]), err_msg="migration reconciliation")
for name, ref, kw in (
    ("collect", rtel, dict(collect=True)),
    ("full", rfull, dict(collect=True,
                         fallback=FallbackConfig(threshold=0.5, lam=0.5),
                         p_od=p_od)),
):
    for shape in MESHES:
        sh = fast_sim.simulate_pool_jobs_sharded(
            rarrs, stacked, TPUT, rp, ra, rpm, delta_mig=1,
            mesh=None if shape is None else make_pool_mesh(shape=shape),
            **kw)
        assert set(sh) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(
                np.asarray(ref[k]), np.asarray(sh[k]),
                err_msg=f"region {name} {k} mesh={shape}")
print("SHARDED-PARITY-OK")
"""


def test_sharded_matches_single_device_4dev_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, os.path.dirname(SRC)] + env.get("PYTHONPATH", "").split(os.pathsep)
    ).rstrip(os.pathsep)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        env=env, timeout=900,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED-PARITY-OK" in out.stdout


def test_make_pool_mesh_shapes():
    """Shape validation + axis naming for the 1-D and 2-D pool meshes."""
    import jax

    from repro.launch.mesh import make_pool_mesh, parse_pool_mesh_shape

    mesh = make_pool_mesh()
    assert mesh.axis_names == ("jobs",)
    assert mesh.devices.shape == (jax.device_count(),)
    mesh2 = make_pool_mesh(shape=(1, 1))
    assert mesh2.axis_names == ("jobs", "lanes")
    with pytest.raises(ValueError):
        make_pool_mesh(shape=(2, 3))  # does not cover 1 device
    with pytest.raises(ValueError):
        make_pool_mesh(shape=(1, 1, 1))
    assert parse_pool_mesh_shape("") is None
    assert parse_pool_mesh_shape("auto") is None
    assert parse_pool_mesh_shape("4") == (4,)
    assert parse_pool_mesh_shape("2x2") == (2, 2)


def test_sharded_single_device_fallback_bitwise():
    """With one visible device the sharded entry point must fall through to
    (and bitwise-match) simulate_pool_jobs, and accept explicit 1-device
    meshes of either rank."""
    import jax

    from benchmarks.common import job_stream
    from repro.configs.base import ThroughputConfig
    from repro.core import fast_sim
    from repro.core.market import vast_like_trace
    from repro.core.policy_pool import (
        baseline_specs,
        paper_pool,
        rand_deadline_pool,
        specs_to_arrays,
    )
    from repro.core.predictor import NoisyPredictor
    from repro.launch.mesh import make_pool_mesh

    assert jax.device_count() == 1
    tput = ThroughputConfig(mu1=0.9, mu2=0.95)
    pool = (paper_pool(omegas=(2,), sigmas=(0.5,))
            + rand_deadline_pool((0.4,)) + baseline_specs())
    arrs = specs_to_arrays(pool)
    rng = np.random.default_rng(3)
    d = 10
    jobs = list(job_stream(rng, 3, deadline=d))
    traces = [vast_like_trace(seed=60 + i, days=1).window(0, d + 1)
              for i in range(3)]
    prices = np.stack([t.prices[:d] for t in traces]).astype(np.float32)
    avail = np.stack([t.avail[:d] for t in traces]).astype(np.int64)
    preds = np.stack([
        NoisyPredictor(t, "fixed_uniform", 0.2, seed=i).matrix(
            fast_sim.W1MAX - 1
        )[:d]
        for i, t in enumerate(traces)
    ]).astype(np.float32)
    stacked = fast_sim.stack_jobs(jobs)
    base = fast_sim.simulate_pool_jobs(arrs, stacked, tput, prices, avail, preds)
    for mesh in (None, make_pool_mesh(), make_pool_mesh(shape=(1, 1))):
        sh = fast_sim.simulate_pool_jobs_sharded(
            arrs, stacked, tput, prices, avail, preds, mesh=mesh
        )
        for k in base:
            np.testing.assert_array_equal(
                np.asarray(base[k]), np.asarray(sh[k]), err_msg=k
            )
