"""Each cell's call path driven through the harness on the CPU at a tiny
size; the faults the check must catch; new cells added as files only; and
the command's refusal to run without a TPU.

The tiny copies keep every traffic mix and configuration but cut the jobs
per call to a handful and the pool to a few lanes. The faults are planted in
the program underneath a run whose pool has no AHAP lanes, so that the sound
run is exactly correct on the CPU too (see test_perfbench_ref.py for why
AHAP cells may take another, equally good plan there).
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

SMALL_POOL = [{"kind": "ahap", "omega": [1, 3], "sigma": [0.5]},
              {"kind": "ahanp", "sigma": [0.5]},
              {"kind": "rand_deadline", "q": [0.5]},
              {"kind": "od_only"}, {"kind": "msu"}, {"kind": "up"}]
CHEAP_POOL = SMALL_POOL[1:]


def make_root(dst, pool):
    """A checkout-shaped copy of the benchmark with tiny traffic."""
    os.makedirs(dst)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", ".traces",
                                                  "__pycache__"))
    for name in os.listdir(os.path.join(dst, "bench", "traffic")):
        path = os.path.join(dst, "bench", "traffic", name)
        with open(path) as f:
            t = json.load(f)
        t.update(jobs_per_call=6 if t["jobs_per_call"] > 1 else 1,
                 check_calls=2)
        if "check_jobs" in t:
            t["check_jobs"] = 3
        with open(path, "w") as f:
            json.dump(t, f)
    for name in os.listdir(os.path.join(dst, "bench", "configs")):
        path = os.path.join(dst, "bench", "configs", name)
        with open(path) as f:
            c = json.load(f)
        c["pool"] = pool
        c["market"]["days"] = 3
        with open(path, "w") as f:
            json.dump(c, f)
    return str(dst)


def run_cell(root, workload, seed=11, seconds=0.3, capsys=None):
    from bench import run

    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", "0"], root=root,
                  require_tpu=False, persistent_cache=False)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("small") / "root", SMALL_POOL)


@pytest.fixture(scope="module")
def cheap_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("cheap") / "root", CHEAP_POOL)


@pytest.mark.parametrize("workload", ["select-paper-1000",
                                      "select-paper-1job"])
def test_cell_call_path(small_root, workload, capsys):
    res, err = run_cell(small_root, workload, capsys=capsys)
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert res["attempted"] >= 1
    m = res["metrics"]
    assert m["decisions_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert ("call_p95_ms" in m) == (workload != "select-paper-1000")
    assert res["device"]["count"] == 1
    tail = err.splitlines()[-len(res["checks"]):]
    for line, (name, row) in zip(tail, res["checks"].items()):
        assert np.isfinite(row["value"]) and row["limit"] > 0
        assert line.startswith(f"check {name} ")


FOUR_DEVICES = r"""
import json, sys
sys.path[:0] = [{repo!r}, {src!r}]
import numpy as np
from bench import run
from repro.core import fast_sim

def go(seed):
    rc = run.main(["--workload", "select-paper-1000-x4", "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "0"], root={root!r},
                  require_tpu=False, persistent_cache=False)
    assert rc == 0

go(5)
if {fault!r}:
    real = fast_sim._run_partitioned_sharded
    def no_exchange(*a, **k):
        out = real(*a, **k)
        u = np.asarray(out["utility"])
        # every shard sees only the first shard's rows
        per = -(-u.shape[0] // 4)
        out = dict(out, utility=np.resize(u[:per], u.shape))
        return out
    fast_sim._run_partitioned_sharded = no_exchange
    go(6)
"""


FOUR_CHIP_CELL = {"name": "select-paper-1000-x4", "config": "paper-sec6a",
                  "traffic": "batch-1000", "chips": 4,
                  "why": "cell 1's batches over a (4,) job mesh"}


@pytest.mark.parametrize("fault", [False, True])
def test_four_chip_cell_on_four_cpu_devices(cheap_root, fault):
    """A cell on four chips lays the job axis over all four: it is added
    as data only, its entry and its limits, the way a later change that
    measures it on four chips (PERF.md) will add it."""
    path = os.path.join(cheap_root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    if FOUR_CHIP_CELL["name"] not in [w["name"] for w in spec["workloads"]]:
        spec["workloads"].append(FOUR_CHIP_CELL)
        with open(path, "w") as f:
            json.dump(spec, f)
        limits = os.path.join(cheap_root, "bench", "limits", "%s.json")
        shutil.copy(limits % "select-paper-1000",
                    limits % FOUR_CHIP_CELL["name"])
    code = FOUR_DEVICES.format(repo=REPO, src=os.path.join(REPO, "src"),
                               root=cheap_root, fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    assert lines[0]["correct"] is True
    assert lines[0]["device"]["count"] == 4
    if fault:
        assert lines[1]["correct"] is False


# ---------------------------------------------------------------------------
# Faults planted in the program under a run: correct must come out false
# ---------------------------------------------------------------------------

def _state_unchanged(monkeypatch):
    from repro.core import engine

    real = engine._normalize_and_scan

    def unchanged(jobs, u, state, *a, **k):
        _, traj = real(jobs, u, state, *a, **k)
        return state, traj

    monkeypatch.setattr(engine, "_normalize_and_scan", unchanged)


def _half_batch(monkeypatch):
    from repro.core import engine

    real = engine._normalize_and_scan

    def half(jobs, u, state, *a, **k):
        n = u.shape[0] // 2
        top = type(jobs)(*[f[:n] for f in jobs])
        st, traj = real(top, u[:n], state, *a, **k)
        return st, {key: np.concatenate([v, np.repeat(v[-1:], u.shape[0] - n,
                                                       axis=0)])
                    for key, v in traj.items()}

    monkeypatch.setattr(engine, "_normalize_and_scan", half)


def _answer_altered(monkeypatch):
    from repro.core import fast_sim

    real = fast_sim._finalize

    def altered(*a, **k):
        out = real(*a, **k)
        return dict(out, utility=out["utility"] + 0.5)

    monkeypatch.setattr(fast_sim, "_finalize", altered)


FAULTS = [
    ("select-paper-1000", _state_unchanged),
    ("select-paper-1000", _half_batch),
    ("select-paper-1000", _answer_altered),
    ("select-paper-1job", _state_unchanged),
    ("select-paper-1job", _answer_altered),
]


@pytest.mark.parametrize("workload,plant", FAULTS,
                         ids=[f"{w}-{p.__name__.strip('_')}"
                              for w, p in FAULTS])
def test_fault_fails_the_check(cheap_root, workload, plant, monkeypatch,
                               capsys):
    import jax

    sound, _ = run_cell(cheap_root, workload, seed=21, capsys=capsys)
    assert sound["correct"] is True
    plant(monkeypatch)
    jax.clear_caches()
    try:
        bad, err = run_cell(cheap_root, workload, seed=22, capsys=capsys)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert bad["correct"] is False, err
    assert bad["failed"] > 0


@pytest.mark.parametrize("workload,jobs,calls", [
    ("select-paper-1000", 12, 3), ("select-paper-1job", 1, 6)])
def test_control_fails_the_check(workload, jobs, calls):
    """The reference one precision lower in the program's place, over a
    window of whole calls with the EG state carried, through the check a
    run gets."""
    from bench import control, harness

    cell = harness.Cell.load(workload)
    entry = cell.entry
    traffic = entry.Traffic(cell.config, dict(
        cell.traffic, jobs_per_call=jobs, check_jobs=4, check_calls=calls), 9)
    kept = entry.control_window(traffic, calls,
                                control.LOWER[cell.config["precision"]])
    numbers = entry.check(traffic, kept, harness.pick(traffic, calls))
    correct, rows = harness.judge(numbers, cell.limits)
    assert correct is False, numbers
    # the float32 reference in the program's place passes
    kept = entry.control_window(traffic, calls, np.float32)
    numbers = entry.check(traffic, kept, harness.pick(traffic, calls))
    assert harness.judge(numbers, cell.limits)[0] is True, numbers


# ---------------------------------------------------------------------------
# Adding a cell is adding files
# ---------------------------------------------------------------------------

NEW_METRIC = '''"""Calls traced (a made-up metric of a later PR)."""


def read(ctx):
    return float(ctx.trace.calls()) or None
'''


def test_new_cell_from_files_only(tmp_path, capsys):
    from bench import harness
    from bench.trace_reduce import from_events

    root = make_root(tmp_path / "root", CHEAP_POOL)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "paper-sec6a.json")) as f:
        cfg = json.load(f)
    cfg.update(name="sec6a-calm", market=dict(cfg["market"], price_sigma=0.2))
    files = {
        ("configs", "sec6a-calm.json"): json.dumps(cfg),
        ("traffic", "batch-8.json"): json.dumps(
            {"jobs_per_call": 8, "check_calls": 2,
             "check_jobs": 2}),
        ("limits", "select-calm-8.json"): json.dumps(
            {"util_off_share": 0.01}),
        ("metrics", "calls_traced.py"): NEW_METRIC,
    }
    for (d, name), text in files.items():
        with open(os.path.join(b, d, name), "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "sec6a-calm", "source": "x",
                            "file": "bench/configs/sec6a-calm.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "select-calm-8",
                              "config": "sec6a-calm",
                              "traffic": "batch-8", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "calls_traced", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "harness", "moves": "decisions_per_s",
                              "workloads": ["select-calm-8"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cell = harness.Cell.load("select-calm-8", root)
    assert cell.config["market"]["price_sigma"] == 0.2
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle_share", "calls_traced"]
    res, _ = run_cell(root, "select-calm-8", capsys=capsys)
    assert res["correct"] is True
    assert res["metrics"]["decisions_per_s"]["value"] > 0
    trace = from_events([("/host:CPU", [("python", [
        ("bench.window", 0.0, 1e9), ("bench.call", 0.0, 5e8),
        ("bench.call", 5e8, 5e8)])])])
    got = harness.layer_metrics(cell, trace, root)
    assert got == {"calls_traced": {"value": 2.0, "unit": "calls"}}


# ---------------------------------------------------------------------------
# No TPU, no result
# ---------------------------------------------------------------------------

def test_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "bench/run.py", "--workload", "select-paper-1000",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr

    bare = tmp_path / "bare"
    os.makedirs(bare)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), bare)
    shutil.copytree(BENCH, bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
    env["PYTHONPATH"] = ""
    p = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
