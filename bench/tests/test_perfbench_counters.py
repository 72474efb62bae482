"""The readers of the program's transfer and launch counters: the process
totals per selection call, and nothing where the program has no counters
(as at a commit before them)."""
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
    traffic = cell.entry.Traffic(cell.config, cell.traffic, 2**31 + 7)
    driver = cell.entry.Driver(cell, traffic, jax.devices()[:1],
                               harness.Spans(False))
    snaps = []
    for inp in (traffic.warmup(0), traffic.call(0), traffic.call(1)):
        driver.call(inp)
        snaps.append(fresh_counters.snapshot())
    # the calls after the first count the same as each other (the first may
    # count more, where the program keeps what it sends on the device)
    steps = [{k: b[k] - a.get(k, 0) for k in b}
             for a, b in zip(snaps, snaps[1:])]
    assert steps[0] == steps[1]
    assert steps[0]["select_calls"] == 1 and steps[0]["launches"] > 0
    # the readers read the process totals over the selection calls
    tot = snaps[-1]
    per = lambda k: tot.get(k, 0) / tot["select_calls"]
    assert _read(root) == pytest.approx({
        "launches": per("launches"), "h2d_arrays": per("h2d_arrays"),
        "h2d_kb": per("h2d_bytes") / 1e3, "d2h_kb": per("d2h_bytes") / 1e3})


def test_counter_readers_without_counters(tmp_path, monkeypatch):
    import repro.obs

    root = make_root(tmp_path / "root", SMALL_POOL)
    monkeypatch.delattr(repro.obs, "counters", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs.counters", None)
    assert set(_read(root).values()) == {None}
