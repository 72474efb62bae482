"""The readers of the program's transfer and launch counters: per
selection call over uniform calls, and nothing where the program has no
counters (as at a commit before them)."""
import sys

import jax
import pytest

from bench import harness
from bench.tests.test_perfbench_cells import SMALL_POOL, make_root

READERS = ("launches", "h2d_arrays", "h2d_kb", "d2h_kb")


def _read(root):
    ctx = harness.MetricContext(trace=None, chips=1)
    return {n: harness.load_reader(n, root)(ctx) for n in READERS}


@pytest.fixture
def fresh_counters(monkeypatch):
    from repro.obs import counters

    monkeypatch.setattr(counters, "_COUNTS", {})
    return counters


@pytest.mark.parametrize("workload", ["select-paper-1000",
                                      "select-paper-1job"])
def test_counter_readers_per_call(tmp_path, fresh_counters, workload):
    root = make_root(tmp_path / "root", SMALL_POOL)
    assert set(_read(root).values()) == {None}       # no call yet
    cell = harness.Cell.load(workload, root)
    traffic = harness.Traffic(cell.config, cell.traffic, 2**31 + 7)
    driver = harness.SelectDriver(cell, traffic, jax.devices()[:1],
                                  harness.Spans(False))
    driver.call(traffic.warmup(0))
    one = fresh_counters.snapshot()
    for i in range(2):
        driver.call(traffic.call(i))
    three = fresh_counters.snapshot()
    # every call of a cell counts the same
    assert three == {k: 3 * v for k, v in one.items()}
    assert _read(root) == pytest.approx({
        "launches": one["launches"], "h2d_arrays": one["h2d_arrays"],
        "h2d_kb": one["h2d_bytes"] / 1e3, "d2h_kb": one["d2h_bytes"] / 1e3})
    # two kind partitions and their scatter-merge, as on the chip
    assert one["launches"] == 23 and one["h2d_arrays"] == 39


def test_counter_readers_without_counters(tmp_path, monkeypatch):
    import repro.obs

    root = make_root(tmp_path / "root", SMALL_POOL)
    monkeypatch.delattr(repro.obs, "counters", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.counters", None)
    assert set(_read(root).values()) == {None}
