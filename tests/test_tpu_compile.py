"""Compiles for a described (not attached) TPU v5e.

The TPU compiler is installed alongside JAX, so the programs of the main
path compile here for the real chip at their real sizes: the Pallas
window-DP kernel through Mosaic (interpret mode cannot refuse a bad tiling
or too much VMEM), the 1000-job AHAP partition program of the selection
engine on both window-DP backends, and the 1000-job fleet scan. Nothing
runs, so this says nothing about results or times; it does catch what the
chip's compiler would refuse, and programs that outgrow the chip's 16 GiB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.common import PAPER_TPUT
from repro.core import fast_sim, fleet
from repro.core.fast_sim import W1MAX, JobArrays
from repro.core.policy_pool import (
    baseline_specs,
    paper_pool,
    rand_deadline_pool,
    specs_to_arrays,
)
from repro.kernels.window_dp import window_dp

HBM_BYTES = 16 * 2**30      # one TPU v5e chip
N_JOBS = 1000               # Fig. 9/10 scale
DEADLINE = 10
TN = fast_sim.NTABLE        # window-DP unit-table width


@pytest.fixture(scope="module")
def one_chip():
    """A single described v5e chip, with JAX's persistent compilation
    cache off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(shape_or_array, dtype=None, *, sharding):
    if dtype is None:
        a = np.asarray(shape_or_array)
        shape_or_array, dtype = a.shape, a.dtype
    return jax.ShapeDtypeStruct(
        tuple(shape_or_array), jax.dtypes.canonicalize_dtype(dtype),
        sharding=sharding)


def _jobs(n, sharding):
    i32, f32 = np.int32, np.float32
    return JobArrays(*(_sds((n,), t, sharding=sharding)
                       for t in (f32, i32, i32, i32, f32, f32, f32)))


def _hbm_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)


@pytest.mark.parametrize("lanes", [105000, 3])
def test_window_dp_kernel_compiles(one_chip, lanes):
    """The kernel at the pool's per-slot batch (1000 jobs x 105 AHAP
    lanes) and at a batch smaller than one lane block."""
    w1 = W1MAX
    compiled = window_dp.lower(
        _sds((lanes, w1, TN + 1), np.float32, sharding=one_chip),
        _sds((lanes, w1 * TN + 1), np.float32, sharding=one_chip),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_pool_ahap_partition_fits_one_chip(one_chip, backend):
    """The selection engine's AHAP partition program at 1000 jobs x the
    124-lane pool's 105 AHAP lanes x 10 slots."""
    arrs = specs_to_arrays(paper_pool() + rand_deadline_pool()
                           + baseline_specs())
    _, _, ahap_args, _ = fast_sim._partition_lane_args(arrs, False)
    compiled = fast_sim._pool_jobs_ahap.lower(
        *(_sds(a, sharding=one_chip) for a in ahap_args),
        _jobs(N_JOBS, one_chip), PAPER_TPUT,
        _sds((N_JOBS, DEADLINE), np.float32, sharding=one_chip),
        _sds((N_JOBS, DEADLINE), np.int32, sharding=one_chip),
        _sds((N_JOBS, DEADLINE, W1MAX, 2), np.float32, sharding=one_chip),
        backend,
    ).compile()
    if backend == "pallas":
        assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) < HBM_BYTES


def test_fleet_scan_fits_one_chip(one_chip):
    """The fleet contention scan at 1000 live jobs, both kind blocks."""
    horizon = 15
    pol = {k: _sds((N_JOBS,), np.int32 if k in ("kind", "omega", "v")
                   else np.float32, sharding=one_chip)
           for k in fleet._POLICY_KEYS}
    i32 = lambda *s: _sds(s, np.int32, sharding=one_chip)
    compiled = fleet._fleet_call.lower(
        pol, _jobs(N_JOBS, one_chip), i32(N_JOBS), PAPER_TPUT,
        _sds((horizon,), np.float32, sharding=one_chip), i32(horizon),
        _sds((horizon, W1MAX, 2), np.float32, sharding=one_chip),
        "xla",
    ).compile()
    assert _hbm_bytes(compiled) < HBM_BYTES
