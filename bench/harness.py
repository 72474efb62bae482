"""The benchmark harness: set up a cell, run its closed loop, trace it,
check its outputs against the reference and report.

Everything that belongs to one cell is found by name: the workload entry
of ``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``)
and a traffic mix (``bench/traffic/<name>.json``); each per-layer metric is
``bench/metrics/<name>.py``; each cell's limits are
``bench/limits/<workload>.json``. The program is reached through
``engine.simulate_and_select``, its job axis laid over the cell's chips.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import time
from dataclasses import dataclass
from typing import Callable, Dict, List

import numpy as np

from bench.gen.traffic import Traffic, lane_arrays
from bench.ref import forecast as ref_forecast
from bench.ref import sched as ref

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(BENCH_DIR, ".traces")

# a (job, lane) utility further than this from the reference's is "off":
# the repo's own python-vs-device tolerance, in the value's units (v = 120)
OFF_TOL = 1e-2


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    """One workload entry with its configuration, traffic and limits."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @staticmethod
    def load(name: str, root: str = ROOT) -> "Cell":
        spec = read_json(root, "BENCHMARK.json")
        found = [w for w in spec["workloads"] if w["name"] == name]
        if len(found) != 1:
            raise KeyError(f"no single workload {name!r} in BENCHMARK.json")
        wl = found[0]
        bench = os.path.join(root, "bench")
        cfg = [c for c in spec["configs"] if c["name"] == wl["config"]][0]
        mine = lambda m: "workloads" not in m or name in m["workloads"]
        return Cell(
            name=name, chips=int(wl["chips"]),
            config=read_json(root, cfg["file"]),
            traffic=read_json(bench, "traffic", wl["traffic"] + ".json"),
            limits=read_json(bench, "limits", name + ".json"),
            end_to_end=[m for m in spec["end_to_end"] if mine(m)],
            per_layer=[m for m in spec["per_layer"] if mine(m)],
        )


def tput_of(config: dict) -> dict:
    return {k: float(v) for k, v in config["throughput"].items()}


def precision(config: dict):
    """The float type the configuration states, which the reference uses."""
    return np.dtype(config["precision"]).type


def eg_eta(config: dict, lanes: int) -> float:
    """The EG learning rate: Thm. 2's for the configuration's horizon."""
    return float(np.sqrt(2.0 * np.log(lanes) / int(config["eg_horizon_jobs"])))


# ---------------------------------------------------------------------------
# The program under test
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


def program_pool(lanes: list) -> dict:
    """The program's lane encoding of a config's lanes, in the same order."""
    from repro.core.policy_pool import KIND_NAMES, PolicySpec, specs_to_arrays

    code = {name: k for k, name in KIND_NAMES.items()}
    return specs_to_arrays([
        PolicySpec(code[l["kind"]], l["omega"], l["v"], l["sigma"])
        for l in lanes])


class SelectDriver:
    """One call: the program's forecast prep for the call's jobs, then
    ``engine.simulate_and_select`` with its default options on a job mesh
    over the cell's chips, the EG state carried from the previous call.
    Utilities come back with the result so that the check can compare
    them."""

    def __init__(self, cell: Cell, traffic: Traffic, devices, spans: Spans):
        from repro.configs.base import ThroughputConfig
        from repro.core import engine, market, selector
        from repro.core.fast_sim import JobArrays
        from repro.launch.mesh import make_pool_mesh

        self.engine, self.JobArrays = engine, JobArrays
        self.tr, self.spans = traffic, spans
        self.pool = program_pool(traffic.lanes)
        self.tput = ThroughputConfig(**tput_of(cell.config))
        self.trace = market.Trace(traffic.prices, traffic.avail)
        self.mesh = make_pool_mesh(devices)
        self.noise = cell.config["noise"]
        self.state0 = selector.eg_init(len(traffic.lanes),
                                       int(cell.config["eg_horizon_jobs"]))
        self.state = self.state0

    def reset(self):
        self.state = self.state0

    def call(self, inp: dict):
        span = self.spans.span
        with span("bench.call"):
            with span("bench.prep"):
                n = self.noise
                prices, avail, preds = self.engine.prepare_noisy_inputs(
                    self.trace, inp["t0s"], self.tr.deadline, n["kind"],
                    n["level"], inp["noise_seeds"], horizon=n["horizon"],
                    avail_max=n["avail_max"])
            with span("bench.engine"):
                jobs = self.JobArrays(**inp["jobs"])
                res = self.engine.simulate_and_select(
                    self.pool, jobs, self.tput, prices, avail, preds,
                    mesh=self.mesh, state=self.state, return_utilities=True)
            with span("bench.fetch"):
                res.state.weights.block_until_ready()
        self.state = res.state
        return res

    # -- check ------------------------------------------------------------

    @staticmethod
    def host_state(s) -> dict:
        """The selector state as float64: the Kahan-compensated sums and
        the weights."""
        f = lambda x: np.asarray(x, np.float64)
        return {"cu": f(s.cum_utils) - f(s.cum_utils_c),
                "ce": float(f(s.cum_expected) - f(s.cum_expected_c)),
                "w": f(s.weights)}

    def keep(self, res):
        """What the check needs of a call: its utilities and regret (host
        arrays already) and its EG state (moved to the host by
        :meth:`settle` once the window has closed)."""
        return {"u": res.utilities, "state": res.state,
                "regret": float(res.regret[-1])}

    def settle(self, kept: List[dict], need) -> None:
        """Move the kept states of the calls in ``need`` to the host and
        drop every device array."""
        for i, k in enumerate(kept):
            k["state"] = self.host_state(k["state"]) if i in need else None


# ---------------------------------------------------------------------------
# The closed loop
# ---------------------------------------------------------------------------

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


def run_window(driver, traffic: Traffic, seconds: float, spans: Spans,
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
# The check against the reference
# ---------------------------------------------------------------------------

def ref_inputs(traffic: Traffic, inp: dict, rows):
    """The reference's own market windows and forecasts for ``rows`` of a
    call, over a job's deadline of d slots: (prices (S, d), avail (S, d),
    pred (S, d, W1, 2))."""
    n = traffic.config["noise"]
    slots = traffic.deadline
    ps, avs, pms = [], [], []
    for r in rows:
        t0 = int(inp["t0s"][r])
        p = traffic.prices[t0:t0 + slots + 1]
        a = traffic.avail[t0:t0 + slots + 1]
        fc = ref_forecast.forecast(p, a, n["kind"], n["level"],
                                   int(inp["noise_seeds"][r]), n["horizon"],
                                   n["avail_max"])
        ps.append(p[:slots])
        avs.append(a[:slots])
        pms.append(fc[:slots])
    return np.stack(ps), np.stack(avs), np.stack(pms)


def pick(traffic: Traffic, n_calls: int):
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


def _util_numbers(gaps: List[np.ndarray]) -> Dict[str, float]:
    g = np.concatenate([np.ravel(x) for x in gaps])
    g = np.where(np.isfinite(g), g, np.inf)
    return {"util_gap_max": float(g.max()),
            "util_off_share": float(np.mean(g > OFF_TOL))}


def check(traffic: Traffic, kept: List[dict], samples) -> Dict[str, float]:
    """Compared numbers of a selection cell's window. Sampled rows: every lane's
    utility against the reference simulator. Each sampled call's EG step:
    the reference's normalisation and EG update over the call's utility
    matrix (its sampled rows replaced by the reference's own), from the
    selector state the call started with, against the program's weights and
    regret after the call. ``eg_sum_gap`` is the widest gap of a policy's
    cumulative normalised utility, the selector's whole state. The first
    call starts from no utility at all."""
    cfg = traffic.config
    ft = precision(cfg)
    lanes = lane_arrays(traffic.lanes)
    tput = tput_of(cfg)
    m = len(traffic.lanes)
    eta = eg_eta(cfg, m)
    initial = {"cu": np.zeros(m), "ce": 0.0}
    gaps, w_gap, cu_gap, r_gap = [], 0.0, 0.0, 0.0
    for c, rows in samples:
        inp = traffic.call(c)
        p, a, pm = ref_inputs(traffic, inp, rows)
        jobs_s = {k: v[rows] for k, v in inp["jobs"].items()}
        u_ref = ref.simulate_cells(jobs_s, lanes, tput, p, a, pm, ft=ft)
        u_prog = np.asarray(kept[c]["u"], np.float64)
        gaps.append(np.abs(u_prog[rows] - u_ref))
        u_mix = u_prog.copy()
        u_mix[rows] = u_ref
        s_in = kept[c - 1]["state"] if c > 0 else initial
        w, cu, _, regret = ref.eg_run(s_in["cu"], s_in["ce"], eta,
                                      ref.normalize(inp["jobs"], u_mix))
        s_out = kept[c]["state"]
        w_gap = max(w_gap, float(np.max(np.abs(s_out["w"] - w))))
        cu_gap = max(cu_gap, float(np.max(np.abs(s_out["cu"] - cu))))
        r_gap = max(r_gap, abs(kept[c]["regret"] - float(regret))
                    / max(1.0, abs(float(regret))))
    out = _util_numbers(gaps)
    out.update(eg_sum_gap=cu_gap, eg_weight_gap=w_gap, eg_regret_gap=r_gap)
    return out


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
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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


def end_to_end(cell: Cell, traffic: Traffic, win: Window,
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
