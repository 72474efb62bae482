"""Each configuration names the program entry its cells drive, and the
harness finds the entry by that name: a cell whose entry is a new module
runs through ``bench/run.py`` and ``bench/control.py`` with new files only,
and a configuration that names no entry, or one that is not there, is
refused as the cell loads."""
import json
import os

import pytest

from bench import harness
from bench.tests.test_perfbench_cells import CHEAP_POOL, make_root

TOY_ENTRY = '''"""A toy entry: each call sums the squares of each row of a matrix
drawn from the seed, in one jitted program."""
import numpy as np

DECISIONS_PER_ROW = 7


class Traffic:
    def __init__(self, config, traffic, seed):
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.jobs_per_call = int(traffic["jobs_per_call"])

    def decisions_per_call(self):
        return DECISIONS_PER_ROW * self.jobs_per_call

    def _rng(self, *tags):
        return np.random.default_rng([self.seed & (2**64 - 1), *tags])

    def _draw(self, tag, i):
        shape = (self.jobs_per_call, int(self.config["width"]))
        return {"x": self._rng(tag, i).uniform(0.5, 1.0, shape)
                .astype(np.float32)}

    def call(self, i):
        return self._draw(1, i)

    def warmup(self, i):
        return self._draw(2, i)

    def sample_rng(self):
        return self._rng(3)


class Driver:
    def __init__(self, cell, traffic, devices, spans):
        import jax
        import jax.numpy as jnp

        self.spans, self.device = spans, devices[0]
        self.f = jax.jit(lambda x: jnp.sum(x * x, axis=1))
        self.put = jax.device_put

    def call(self, inp):
        with self.spans.span("bench.call"):
            x = self.put(inp["x"], self.device)
            return self.f(x).block_until_ready()

    def reset(self):
        pass

    def keep(self, out):
        return {"y": out}

    def settle(self, kept, need):
        for i, k in enumerate(kept):
            k["y"] = np.asarray(k["y"], np.float64) if i in need else None


def check(traffic, kept, samples):
    gap = 0.0
    for c, rows in samples:
        x = np.asarray(traffic.call(c)["x"], np.float64)[rows]
        want = np.sum(x * x, axis=1)
        gap = max(gap, float(np.max(np.abs(kept[c]["y"][rows] - want)
                                    / want)))
    return {"sum_rel_gap": gap}


def control_window(traffic, n_calls, low):
    out = []
    for c in range(n_calls):
        x = traffic.call(c)["x"].astype(low)
        out.append({"y": np.sum(x * x, axis=1, dtype=low)
                    .astype(np.float64)})
    return out
'''


def _toy_root(root):
    """``root`` with a toy cell added as new files and one workload entry."""
    b = os.path.join(root, "bench")
    files = {
        ("entries", "toy.py"): TOY_ENTRY,
        ("configs", "toy-rows.json"): json.dumps(
            {"name": "toy-rows", "entry": "toy", "precision": "float32",
             "width": 64}),
        ("traffic", "rows-8.json"): json.dumps(
            {"jobs_per_call": 8, "check_calls": 2, "check_jobs": 4}),
        ("limits", "toy-rows-8.json"): json.dumps({"sum_rel_gap": 1e-5}),
    }
    for (d, name), text in files.items():
        with open(os.path.join(b, d, name), "w") as f:
            f.write(text)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-rows", "source": "x",
                            "file": "bench/configs/toy-rows.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "toy-rows-8", "config": "toy-rows",
                              "traffic": "rows-8", "chips": 1, "why": "x"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return _toy_root(make_root(tmp_path_factory.mktemp("toy") / "root",
                               CHEAP_POOL))


def test_new_entry_runs_from_files_only(toy_root, capsys):
    from bench import control, run

    rc = run.main(["--workload", "toy-rows-8", "--seed", str(2**31 + 5),
                   "--seconds", "0.3", "--trace", "0"], root=toy_root,
                  require_tpu=False, persistent_cache=False)
    out = capsys.readouterr()
    assert rc == 0, out.err
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True, out.err
    assert list(res["checks"]) == ["sum_rel_gap"]
    info = json.loads([l for l in out.err.splitlines()
                       if l.startswith("bench: {")][-1][len("bench: "):])
    # the rate counts the toy's own decisions per call
    assert res["metrics"]["decisions_per_s"]["value"] == pytest.approx(
        info["calls"] * 7 * 8 / info["window_s"])
    assert info["compiles_in_window"] == 0

    # the toy's control, bfloat16 in the program's place, goes through the
    # same check and limits, and the check reads it as not correct
    assert control.main(["--workload", "toy-rows-8", "--seed", "3",
                         "--seed", "4"], root=toy_root) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["seed"] for l in lines] == [3, 4]
    assert all(l["control_correct"] is False for l in lines), lines
    assert all(l["numbers"]["sum_rel_gap"] > 1e-4 for l in lines)


@pytest.mark.parametrize("entry,error", [(None, KeyError),
                                         ("no_such_entry", ValueError)])
def test_config_without_a_known_entry_is_refused(tmp_path, entry, error):
    root = _toy_root(make_root(tmp_path / "root", CHEAP_POOL))
    path = os.path.join(root, "bench", "configs", "toy-rows.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.pop("entry")
    if entry:
        cfg["entry"] = entry
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(error, match="toy-rows") as e:
        harness.Cell.load("toy-rows-8", root)
    if entry:
        assert entry in str(e.value)
    # and the command refuses the cell without a result
    from bench import run

    assert run.main(["--workload", "toy-rows-8", "--seed", "1", "--seconds",
                     "0.1", "--trace", "0"], root=root, require_tpu=False,
                    persistent_cache=False) == 2


@pytest.mark.parametrize("workload", ["select-paper-1000",
                                      "select-paper-1job"])
def test_existing_cells_drive_the_select_entry(workload):
    cell = harness.Cell.load(workload)
    assert cell.config["entry"] == "select"
    assert os.path.basename(cell.entry.__file__) == "select.py"
    for name in ("Traffic", "Driver", "check", "control_window"):
        assert callable(getattr(cell.entry, name)), name
