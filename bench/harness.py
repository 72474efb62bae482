"""The benchmark harness: set up a cell, run its closed loop, trace it,
check its outputs against the reference and report.

Everything that belongs to one cell is found by name: the workload entry
of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); each per-layer metric is
``bench/metrics/<name>.py``; each cell's limits are
``bench/limits/<workload>.json``. Each configuration names its entry, the
program entry point its cells drive: ``bench/entries/<entry>.py``, with
the entry's traffic generator, driver, check and control. What is here is
what every entry shares.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import time
from dataclasses import dataclass
from types import ModuleType
from typing import Callable, Dict, List

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(BENCH_DIR, ".traces")


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload entry with its configuration, traffic and limits, and
    the entry module its configuration names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    entry: ModuleType

    @staticmethod
    def load(name: str, root: str = ROOT) -> "Cell":
        spec = read_json(root, "BENCHMARK.json")
        found = [w for w in spec["workloads"] if w["name"] == name]
        if len(found) != 1:
            raise KeyError(f"no single workload {name!r} in BENCHMARK.json")
        wl = found[0]
        bench = os.path.join(root, "bench")
        cfg = [c for c in spec["configs"] if c["name"] == wl["config"]][0]
        config = read_json(root, cfg["file"])
        mine = lambda m: "workloads" not in m or name in m["workloads"]
        return Cell(
            name=name, chips=int(wl["chips"]),
            config=config,
            traffic=read_json(bench, "traffic", wl["traffic"] + ".json"),
            limits=read_json(bench, "limits", name + ".json"),
            end_to_end=[m for m in spec["end_to_end"] if mine(m)],
            per_layer=[m for m in spec["per_layer"] if mine(m)],
            entry=load_entry(cfg["name"], config, root),
        )


def load_module(root: str, kind: str, name: str) -> ModuleType:
    """``bench/<kind>/<name>.py`` under ``root``, imported on its own."""
    path = os.path.join(root, "bench", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_entry(config_name: str, config: dict, root: str) -> ModuleType:
    """The entry module a configuration names under ``"entry"``."""
    entry = config.get("entry")
    if not entry:
        raise KeyError(f"configuration {config_name!r} names no entry")
    if not os.path.isfile(os.path.join(root, "bench", "entries",
                                       f"{entry}.py")):
        raise ValueError(f"configuration {config_name!r} names entry "
                         f"{entry!r}, and there is no "
                         f"bench/entries/{entry}.py")
    return load_module(root, "entries", entry)


# ---------------------------------------------------------------------------
# The closed loop
# ---------------------------------------------------------------------------

class Spans:
    """Host spans the profiler records (no-ops when not tracing)."""

    def __init__(self, on: bool):
        import contextlib

        if on:
            import jax

            self.span = jax.profiler.TraceAnnotation
        else:
            self.span = lambda name: contextlib.nullcontext()


class CompileCounter:
    """Counts XLA compilations (JAX's compile-time events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1


@dataclass
class Window:
    seconds: float
    latencies: List[float]
    kept: List[dict]
    compiles: int


def run_window(driver, traffic, seconds: float, spans: Spans,
               counter: CompileCounter) -> Window:
    """Back-to-back calls until ``seconds`` have passed; each call's inputs
    are drawn before its clock starts."""
    lat, kept = [], []
    c0 = counter.n
    with spans.span("bench.window"):
        t0 = time.perf_counter()
        i = 0
        while True:
            inp = traffic.call(i)
            a = time.perf_counter()
            out = driver.call(inp)
            b = time.perf_counter()
            lat.append(b - a)
            kept.append(driver.keep(out))
            i += 1
            if b - t0 >= seconds:
                break
    return Window(b - t0, lat, kept, counter.n - c0)


# ---------------------------------------------------------------------------
# The check: which answers an entry's check compares, and the verdict
# ---------------------------------------------------------------------------

def pick(traffic, n_calls: int):
    """The calls (and the job rows of each) the check compares,
    drawn from the seed; the last call of the window is always among them,
    so is the first when there are two or more."""
    rng = traffic.sample_rng()
    want = min(int(traffic.traffic["check_calls"]), n_calls)
    calls = {n_calls - 1, 0} if want > 1 else {n_calls - 1}
    rest = [c for c in range(n_calls) if c not in calls]
    calls |= set(rng.choice(rest, size=max(0, want - len(calls)),
                            replace=False).tolist()) if rest else set()
    k = traffic.jobs_per_call
    per = min(int(traffic.traffic.get("check_jobs", k)), k)
    return [(c, np.sort(rng.choice(k, size=per, replace=False)))
            for c in sorted(calls)]


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {value, limit}}) for every number with a limit."""
    rows = {k: {"value": numbers[k], "limit": float(v)}
            for k, v in limits.items()}
    ok = all(np.isfinite(r["value"]) and r["value"] <= r["limit"]
             for r in rows.values())
    return ok, rows


# ---------------------------------------------------------------------------
# Per-layer metrics from a traced window
# ---------------------------------------------------------------------------

@dataclass
class MetricContext:
    trace: object           # trace_reduce.Reduced
    chips: int


def load_reader(name: str, root: str = ROOT) -> Callable:
    return load_module(root, "metrics", name).read


def layer_metrics(cell: Cell, reduced, root: str = ROOT) -> dict:
    ctx = MetricContext(reduced, cell.chips)
    out = {}
    for m in cell.per_layer:
        v = load_reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def breakdown(reduced, chips: int) -> dict:
    """Top device programs and ops by time on the first chip, and the idle
    time of the cell's chips summed by what the host was doing."""
    from bench.trace_reduce import module_base

    dev = reduced.devices[0]
    tot: Dict[str, float] = {}
    for n, s, e in dev.modules:
        key = "module " + module_base(n)
        tot[key] = tot.get(key, 0.0) + (e - s) / 1e9
    ops: Dict[str, float] = {}
    for n, s, e in dev.ops:
        key = "op " + n.split(" = ", 1)[0].lstrip("%")
        ops[key] = ops.get(key, 0.0) + (e - s) / 1e9
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:5]
    top += sorted(ops.items(), key=lambda kv: -kv[1])[:10 - len(top)]
    idle: Dict[str, float] = {}
    for d in reduced.devices[:chips]:
        for label, s in reduced.idle_gaps(d):
            idle[label] = idle.get(label, 0.0) + s / chips
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_trace(trace_dir: str):
    import glob

    from bench import trace_reduce

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    return trace_reduce.load(paths[0])


def fresh_trace_dir(name: str) -> str:
    d = os.path.join(TRACE_DIR, name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    return d


def end_to_end(cell: Cell, traffic, win: Window,
               setup_s: float) -> dict:
    """The cell's end-to-end metrics from the untraced window: decisions
    completed over the window's seconds, the 95th percentile of every
    call's latency, and the set-up time."""
    lat = np.asarray(win.latencies)
    values = {
        "decisions_per_s": len(lat) * traffic.decisions_per_call()
        / win.seconds,
        "call_p95_ms": float(np.percentile(lat, 95)) * 1e3,
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
