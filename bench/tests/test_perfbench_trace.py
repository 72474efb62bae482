"""The trace reduction on a small hand-built trace: busy time as a union of
op intervals, device time per program, idle gaps labelled by the host span
they fall in, and the per-layer metric readers over it."""
import pytest

from bench import harness
from bench.trace_reduce import from_events, module_base, union

MS = 1e6  # ns


def _trace(n_dev=1):
    """A 100 ms window with two calls. Device ops: 10-30 ms (two
    overlapping ops), 50-60 and 70-80 ms; a collective inside the second
    call. Host: prep 0-10 and 40-50, engine 10-35 and 50-85, fetch 35-40."""
    devices = []
    for d in range(n_dev):
        modules = [("jit__pool_jobs_ahap(11)", 10 * MS, 20 * MS),
                   ("jit__normalize_and_scan(3)", 50 * MS, 10 * MS),
                   ("jit__pool_jobs_ahap(11)", 70 * MS, 10 * MS),
                   ("jit_local(9)", 70 * MS, 5 * MS)]
        ops = [("%while.1 = (f32[4]) while(...)", 10 * MS, 15 * MS),
               ("%fusion.2 = f32[4] fusion(...)", 20 * MS, 10 * MS),
               ("%all-gather.3 = f32[8] all-gather(...)", 50 * MS, 2 * MS),
               ("%fusion.4 = f32[4] fusion(...)", 52 * MS, 8 * MS),
               ("%while.5 = (f32[4]) while(...)", 70 * MS, 10 * MS)]
        devices.append((f"/device:TPU:{d}", [("XLA Modules", modules),
                                             ("XLA Ops", ops)]))
    host = ("/host:CPU", [("python", [
        ("bench.window", 0.0, 100 * MS),
        ("bench.call", 0.0, 40 * MS), ("bench.call", 40 * MS, 50 * MS),
        ("bench.prep", 0.0, 10 * MS), ("bench.prep", 40 * MS, 10 * MS),
        ("bench.engine", 10 * MS, 25 * MS), ("bench.engine", 50 * MS, 35 * MS),
        ("bench.fetch", 35 * MS, 5 * MS),
        ("other", 0.0, 1 * MS),
    ])])
    return from_events(devices + [host])


def test_union_and_busy():
    assert union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    tr = _trace()
    dev = tr.devices[0]
    assert tr.window_s() == pytest.approx(0.1)
    assert tr.busy_s(dev) == pytest.approx(0.04)   # 10-30, 50-60, 70-80
    assert tr.calls() == 2
    assert "other" not in tr.spans


def test_module_time():
    tr = _trace()
    dev = tr.devices[0]
    assert module_base("jit__pool_jobs_ahap(11)") == "_pool_jobs_ahap"
    assert tr.module_s(dev, "_pool_jobs_ahap") == pytest.approx(0.03)
    assert tr.module_s(dev, "_normalize_and_scan") == pytest.approx(0.01)
    assert tr.module_s(dev, "_pool_jobs_cheap") == 0.0


def test_gaps_labelled_by_host_span():
    tr = _trace()
    gaps = tr.idle_gaps(tr.devices[0])
    got = [(label, round(s * 1e3, 6)) for label, s in gaps]
    # 0-10 prep, 30-50 (midpoint 40: prep of the second call), 60-70 engine,
    # 80-100 (midpoint 90: after every span) harness
    assert got == [("prep", 10.0), ("prep", 20.0), ("engine call", 10.0),
                   ("harness", 20.0)]


def test_readers_over_the_trace():
    tr = _trace(n_dev=2)
    cell = harness.Cell.load("select-paper-1000")
    cell.chips = 2
    cell.per_layer = [{"name": n, "unit": "ms"} for n in (
        "device_idle_share", "prep_ms", "ahap_scan_ms", "cheap_scan_ms",
        "select_ms")]
    m = {k: v["value"] for k, v in harness.layer_metrics(cell, tr).items()}
    assert m["device_idle_share"] == pytest.approx(60.0)
    assert m["prep_ms"] == pytest.approx(10.0)
    assert m["ahap_scan_ms"] == pytest.approx(15.0)
    assert m["select_ms"] == pytest.approx(5.0)
    # nothing to read: the reader returns nothing and the metric is left out
    assert "cheap_scan_ms" not in m


def test_breakdown():
    tr = _trace(n_dev=2)
    b = harness.breakdown(tr, chips=2)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "module _pool_jobs_ahap"
    assert "op all-gather.3" in names
    assert len(b["device_ops"]) <= 10
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"prep": 0.03, "engine call": 0.01, "harness": 0.02})
