"""The reader of the program's forecast-row counters: per call, with each
cell's own jobs per call, 100 where the batch's noise generators are seeded
in one pass and 0 for the per-job call; nothing where the program has no
counters or counts no forecast rows (as at a commit before them)."""
import json
import os
import sys

import jax
import pytest

from bench import harness
from bench.tests.test_perfbench_cells import REPO, SMALL_POOL, make_root


def _read(root):
    ctx = harness.MetricContext(trace=None, chips=1)
    return harness.load_reader("forecast_batched_share", root)(ctx)


def _cells_traffic(root):
    """A tiny copy whose traffic keeps each cell's own jobs per call."""
    for name in os.listdir(os.path.join(REPO, "bench", "traffic")):
        with open(os.path.join(REPO, "bench", "traffic", name)) as f:
            jobs = json.load(f)["jobs_per_call"]
        path = os.path.join(root, "bench", "traffic", name)
        with open(path) as f:
            t = json.load(f)
        t["jobs_per_call"] = jobs
        with open(path, "w") as f:
            json.dump(t, f)
    return root


@pytest.fixture
def fresh_counters(monkeypatch):
    from repro.obs import counters

    monkeypatch.setattr(counters, "_COUNTS", {})
    return counters


@pytest.mark.parametrize("workload,share", [("select-paper-1000", 100.0),
                                            ("select-paper-1job", 0.0)])
def test_forecast_share_per_call(tmp_path, fresh_counters, workload, share):
    root = _cells_traffic(make_root(tmp_path / "root", SMALL_POOL))
    assert _read(root) is None                       # no call yet
    cell = harness.Cell.load(workload, root)
    traffic = cell.entry.Traffic(cell.config, cell.traffic, 2**31 + 7)
    driver = cell.entry.Driver(cell, traffic, jax.devices()[:1],
                               harness.Spans(False))
    for i in range(2):
        driver.call(traffic.call(i))
        c = fresh_counters.snapshot()
        assert c["forecast_rows"] == (i + 1) * cell.traffic["jobs_per_call"]
        assert _read(root) == share


def test_forecast_share_without_counters(tmp_path, monkeypatch):
    import repro.obs

    root = make_root(tmp_path / "root", SMALL_POOL)
    monkeypatch.delattr(repro.obs, "counters", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.counters", None)
    assert _read(root) is None
