"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Sets up the cell named in ``BENCHMARK.json``
through the entry its configuration names (``bench/entries/<entry>.py``:
inputs from ``--seed``, one warm-up of every shape), runs back-to-back
calls for ``--seconds``, checks sampled outputs with the entry's check
against the plain reference and prints one JSON line last on standard
output. ``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window with the profiler and reports its per-layer metrics.
``setup_s`` runs from the process's start to the window's, less the TPU
runtime's start (JAX's first look for its devices), in which no code of
the repository runs.
Exits 2 without a result when JAX finds no TPU or fewer chips than the
cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# libtpu logs to a fixed directory under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return code


def setup_jax(root: str):
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    # every program this run compiles, however small, is kept for the next
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None, root: str = ROOT, require_tpu: bool = True,
         persistent_cache: bool = True) -> int:
    """``require_tpu`` and ``persistent_cache`` are off only in the tests,
    which drive a run on the CPU at a tiny size."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        cell = harness.Cell.load(args.workload, root)
    except (OSError, KeyError, IndexError, ValueError) as e:
        return fail(f"cannot load workload {args.workload!r}: {e!r}")

    if persistent_cache:
        jax = setup_jax(root)
    else:
        import jax
    phases = {"imports": time.perf_counter() - T_PROCESS}
    devices = jax.devices()
    phases["runtime_init"] = time.perf_counter() - T_PROCESS
    runtime_s = phases["runtime_init"] - phases["imports"]
    if require_tpu and devices[0].platform != "tpu":
        return fail(f"no TPU (JAX found {devices[0].platform}); no result")
    if len(devices) < cell.chips:
        return fail(f"cell needs {cell.chips} chips, found {len(devices)}")
    devices = devices[:cell.chips]
    counter = harness.CompileCounter()

    # ---- set-up: inputs from the seed, one warm-up per shape ----------
    traffic = cell.entry.Traffic(cell.config, cell.traffic, args.seed)
    spans = harness.Spans(bool(args.trace))
    driver = cell.entry.Driver(cell, traffic, devices, spans)
    phases["inputs"] = time.perf_counter() - T_PROCESS
    # the second warm-up call starts from the state the first returned, as
    # every call of the window does
    for i in range(2):
        driver.call(traffic.warmup(i))
        phases[f"warmup_{i}"] = time.perf_counter() - T_PROCESS
    driver.reset()
    setup_s = time.perf_counter() - T_PROCESS - runtime_s
    setup_compiles = counter.n

    # ---- the measured window ------------------------------------------
    seconds = args.seconds
    trace_dir = None
    if args.trace:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds",
                                                      seconds)))
        trace_dir = harness.fresh_trace_dir(cell.name)
        jax.profiler.start_trace(trace_dir)
    try:
        win = harness.run_window(driver, traffic, seconds, spans, counter)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)

    # ---- check against the reference, program state let go -----------
    samples = harness.pick(traffic, len(win.kept))
    need = {c for c, _ in samples} | {c - 1 for c, _ in samples}
    driver.settle(win.kept, need)
    del driver
    t_check = time.perf_counter()
    numbers = cell.entry.check(traffic, win.kept, samples)
    correct, rows = harness.judge(numbers, cell.limits)
    check_s = time.perf_counter() - t_check

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(win.latencies),
              "failed": 0 if correct else len(samples)}
    if args.trace:
        reduced = harness.reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = harness.layer_metrics(cell, reduced, root)
        devs = reduced.devices[:cell.chips]
        if len(devs) < cell.chips:
            return fail(f"the trace holds {len(devs)} TPU planes, the cell "
                        f"uses {cell.chips}", 1)
        device["busy_s"] = sum(reduced.busy_s(d) for d in devs) / len(devs)
        device["window_s"] = reduced.window_s()
        result["breakdown"] = harness.breakdown(reduced, cell.chips)
    else:
        metrics = harness.end_to_end(cell, traffic, win, setup_s)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = rows

    info = {"setup_s": setup_s, "runtime_start_s": runtime_s,
            "setup_phases": phases,
            "setup_compiles": setup_compiles,
            "compiles_in_window": win.compiles, "calls": len(win.latencies),
            "window_s": win.seconds, "check_s": check_s,
            "checked_calls": [c for c, _ in samples], "numbers": numbers}
    print("bench: " + json.dumps(info), file=sys.stderr)
    for name, r in rows.items():
        verdict = "ok" if r["value"] <= r["limit"] else "FAIL"
        print(f"check {name} {r['value']!r} limit {r['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
