"""The fleet cell, ``fleet-paper-1000``, on the CPU at a small size.

A tiny copy of the benchmark keeps the cell's configuration and traffic
but cuts the fleet to 40 jobs and the pool's supply in the same measure
(the paper market's availability x 40), so that the waterfall still decides
who gets spot. On it: the program agrees with the plain reference fleet
(``bench/ref/fleet.py``) through the harness's own check; its grants obey
the waterfall's invariants; faults planted in the program (grants in the
reverse order, grants not clipped to the supply) and the control (the
reference in bfloat16) make ``correct`` false; a program that compiles per
policy mix is refused at set-up; the same seed gives the same calls; and
the cell loads the ``fleet`` entry with 10,000 decisions a call.
"""
import json
import os
import shutil

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "fleet-paper-1000"
JOBS = 40


def tiny_root(dst):
    """A checkout-shaped copy of the benchmark whose fleet cell has
    ``JOBS`` jobs a call on a pool cut in the same measure."""
    os.makedirs(dst)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", ".traces",
                                                  "__pycache__"))
    path = os.path.join(dst, "bench", "traffic", "fleet-burst-1000.json")
    with open(path) as f:
        t = json.load(f)
    t.update(jobs_per_call=JOBS, check_jobs=JOBS)
    with open(path, "w") as f:
        json.dump(t, f)
    path = os.path.join(dst, "bench", "configs", "paper-sec3a-fleet.json")
    with open(path) as f:
        c = json.load(f)
    cut = JOBS / 1000
    m = c["market"]
    m.update(days=3, avail_mean=m["avail_mean"] * cut,
             avail_season_amp=m["avail_season_amp"] * cut,
             avail_sigma=m["avail_sigma"] * cut,
             avail_max=int(m["avail_max"] * cut))
    c["noise"]["avail_max"] = int(c["noise"]["avail_max"] * cut)
    with open(path, "w") as f:
        json.dump(c, f)
    return str(dst)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("fleet") / "root")


@pytest.fixture(scope="module")
def tiny(root):
    """The tiny cell, its traffic at one seed and a driver on the CPU."""
    import jax

    from bench import harness

    cell = harness.Cell.load(CELL, root)
    traffic = cell.entry.Traffic(cell.config, cell.traffic, 2**33 + 5)
    driver = cell.entry.Driver(cell, traffic, jax.devices()[:1],
                               harness.Spans(False))
    return cell, traffic, driver


def run_cell(root, seed, capsys):
    from bench import run

    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   "0.3", "--trace", "0"], root=root, require_tpu=False,
                  persistent_cache=False)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return json.loads(out.out.strip().splitlines()[-1])


def test_cell_loads_the_fleet_entry():
    from bench import harness

    cell = harness.Cell.load(CELL)
    assert os.path.basename(cell.entry.__file__) == "fleet.py"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["decisions_per_s",
                                                    "setup_s"]
    assert {"device_idle_share", "fleet_scan_ms", "fleet_pad_share",
            "fleet_live_share"} <= {m["name"] for m in cell.per_layer}
    traffic = cell.entry.Traffic(cell.config, cell.traffic, 7)
    assert traffic.jobs_per_call == 1000
    assert traffic.decisions_per_call() == 10_000


def test_same_seed_same_calls(tiny):
    cell, traffic, _ = tiny
    again = cell.entry.Traffic(cell.config, cell.traffic, traffic.seed)
    other = cell.entry.Traffic(cell.config, cell.traffic, traffic.seed + 1)
    np.testing.assert_array_equal(traffic.avail, again.avail)
    for draw in ("call", "warmup"):
        for i in (0, 3):
            a, b = (getattr(t, draw)(i) for t in (traffic, again))
            assert a["t0"] == b["t0"] and a["noise_seed"] == b["noise_seed"]
            for key in ("arrivals", "lanes"):
                np.testing.assert_array_equal(a[key], b[key])
            for key in a["jobs"]:
                np.testing.assert_array_equal(a["jobs"][key],
                                              b["jobs"][key])
    assert traffic.call(0)["noise_seed"] != other.call(0)["noise_seed"]
    first = traffic.call(0)["arrivals"]
    assert first.min() >= 0 and first.max() < traffic.arrival_span


def test_program_agrees_with_reference(tiny):
    """Every job's utility and every (job, slot) spot grant of three calls
    equal the reference fleet's, and no slot grants more than its
    supply."""
    cell, traffic, driver = tiny
    kept = [driver.keep(driver.call(traffic.call(c))) for c in range(3)]
    driver.settle(kept, {0, 1, 2})
    rows = np.arange(JOBS)
    numbers = cell.entry.check(traffic, kept, [(c, rows) for c in range(3)])
    assert numbers == {"util_gap_max": numbers["util_gap_max"],
                       "util_off_share": 0.0, "spot_off_share": 0.0,
                       "over_grant": 0.0}
    assert numbers["util_gap_max"] <= 1e-3


def test_waterfall_invariants(tiny):
    """On the program's flight recorder: no slot grants more than its
    supply or a job more than it asked, the supply binds in some slot,
    and in each slot the demanders in (slack, id) order are served in
    full, then at most one in part, then not at all."""
    from repro.core import engine, fleet

    cell, traffic, driver = tiny
    n = cell.config["noise"]
    binds = 0
    for c in range(3):
        inp = traffic.call(c)
        prices, avail, preds = engine.prepare_noisy_inputs(
            driver.trace, np.array([inp["t0"]]), traffic.horizon, n["kind"],
            n["level"], np.array([inp["noise_seed"]]),
            horizon=n["horizon"], avail_max=n["avail_max"])
        rows = {k: v[inp["lanes"]] for k, v in driver.pool.items()}
        out = fleet.simulate_fleet(
            rows, driver.JobArrays(**inp["jobs"]), inp["arrivals"],
            driver.tput, prices[0], avail[0], preds[0], collect=True)
        dem, grant, slack = (np.asarray(out[k]) for k in (
            "tel_demand", "tel_grant", "tel_slack"))
        assert np.all(grant <= dem) and np.all(grant >= 0)
        assert np.all(grant.sum(axis=0) <= avail[0])
        for t in range(traffic.horizon):
            on = np.flatnonzero(dem[:, t] > 0)
            order = on[np.lexsort((on, slack[on, t]))]
            short = grant[order, t] < dem[order, t]
            if short.any():
                binds += 1
                k = int(np.argmax(short))
                assert np.all(grant[order[k + 1:], t] == 0), (c, t)
                assert grant[order, t].sum() == avail[0][t]
    assert binds > 0


def test_sound_run_is_correct(root, capsys):
    res = run_cell(root, 2**40 + 3, capsys)
    assert res["correct"] is True
    assert res["checks"]["over_grant"]["value"] == 0


def _reversed(real):
    return lambda demand, slack, ids, supply: real(demand, -slack, -ids,
                                                   supply)


def _unclipped(real):
    return lambda demand, slack, ids, supply: demand


@pytest.mark.parametrize("fault", [_reversed, _unclipped])
def test_planted_fault_makes_correct_false(root, fault, capsys,
                                           monkeypatch):
    from repro.core import fleet

    monkeypatch.setattr(fleet, "_waterfall", fault(fleet._waterfall))
    fleet._fleet_call.clear_cache()
    try:
        res = run_cell(root, 2**40 + 3, capsys)
    finally:
        monkeypatch.undo()
        fleet._fleet_call.clear_cache()
    assert res["correct"] is False


def test_program_that_compiles_per_mix_is_refused(root, monkeypatch):
    """A program that compiles one more program for each AHAP count, as a
    kind-blocked fleet does, stops the run at set-up, before any window."""
    import jax
    import jax.numpy as jnp

    from bench import run
    from repro.core import fleet
    from repro.core.policy_pool import KIND_AHAP

    per_count = jax.jit(lambda n: jnp.zeros(()) + n, static_argnums=0)
    real = fleet.simulate_fleet_sharded

    def blocked(rows, *args, **kw):
        per_count(int(np.sum(np.asarray(rows["kind"]) == KIND_AHAP)))
        return real(rows, *args, **kw)

    monkeypatch.setattr(fleet, "simulate_fleet_sharded", blocked)
    with pytest.raises(SystemExit, match="compiled 1 more program"):
        run.main(["--workload", CELL, "--seed", str(2**40 + 3),
                  "--seconds", "0.3", "--trace", "0"], root=root,
                 require_tpu=False, persistent_cache=False)


def test_bfloat16_control_fails(root, capsys):
    from bench import control

    assert control.main(["--workload", CELL, "--seed", "11", "--seed",
                         str(2**35)], root=root) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert len(lines) == 2
    assert all(l["control_correct"] is False for l in lines)
