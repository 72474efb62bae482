"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Run:
    PYTHONPATH=src python -m benchmarks.run [--only fig5,fig9] \
        [--json out.json]

``--json <path>`` additionally captures every module's rows as a
machine-readable payload ``{schema_version, backend, devices, elapsed_s,
provenance, rows: [{module, name, us_per_call, derived}, ...]}`` — the
mechanism behind the repo's ``BENCH_*.json`` perf-trajectory files and the
opt-in CI regression guard (tests/test_bench_regression.py reads the
pool_sim speedup rows from it). ``provenance`` pins what produced the
numbers: git sha, jax/python versions, platform, device count, UTC
timestamp.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

# bump when the --json payload layout changes shape
JSON_SCHEMA_VERSION = 2


def provenance() -> dict:
    """Best-effort environment fingerprint for the --json payload. Every
    field degrades to None rather than failing the benchmark run."""
    import platform as _platform
    import subprocess

    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10,
        ).stdout.strip() or None
    except Exception:
        sha = None
    try:
        import jax
        jax_version = jax.__version__
        device_count = jax.device_count()
    except Exception:
        jax_version = device_count = None
    return {
        "git_sha": sha,
        "jax_version": jax_version,
        "python_version": _platform.python_version(),
        "platform": _platform.platform(),
        "device_count": device_count,
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }

MODULES = [
    "fig1_throughput",
    "fig2_market",
    "fig3_arima",
    "fig4_toy",
    "fig5_deadline",
    "fig6_reconfig",
    "fig7_availability",
    "fig8_price",
    "fig9_convergence",
    "fig10_adaptation",
    "theorem1",
    "beyond_robust",
    "predictor_value",
    "theorem2",
    "kernels_bench",
    "pool_sim_bench",
    "region_sim",
    "region_e2e",
    "selection_e2e",
    "fleet_sim",
    "scenario_grid",
    "chaos_sweep",
]


def select_modules(only: str):
    """Resolve a comma-separated ``--only`` prefix list against MODULES.
    Returns ``(selected, unknown)`` — ``unknown`` holds every prefix that
    matched nothing, so a typo (``--only pool_sim,felt_sim``) is an error
    callers can surface instead of a silently skipped benchmark."""
    sel = [s for s in only.split(",") if s]
    selected = [m for m in MODULES
                if not sel or any(m.startswith(s) for s in sel)]
    unknown = [s for s in sel if not any(m.startswith(s) for m in MODULES)]
    return selected, unknown


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated module prefixes")
    ap.add_argument("--json", default="",
                    help="also write all rows to this path as JSON")
    args = ap.parse_args()
    selected, unknown = select_modules(args.only)
    if unknown:
        raise SystemExit(
            f"unknown benchmark name(s): {', '.join(unknown)}\n"
            f"known modules: {', '.join(MODULES)}"
        )

    print("name,us_per_call,derived")
    failures = 0
    json_rows = []
    t_start = time.time()
    for mod_name in selected:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run()
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived:.6g}")
                json_rows.append({
                    "module": mod_name, "name": name,
                    "us_per_call": float(us), "derived": float(derived),
                })
        except Exception as e:
            failures += 1
            print(f"{mod_name},0.0,nan  # FAILED", flush=True)
            json_rows.append({
                "module": mod_name, "name": f"{mod_name}__FAILED",
                "us_per_call": 0.0, "derived": None,  # null: strict-JSON safe
                "error": f"{type(e).__name__}: {e}",
            })
            traceback.print_exc(file=sys.stderr)
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", flush=True)
    if args.json:
        import jax  # benchmark modules have long since initialized it

        payload = {
            "schema_version": JSON_SCHEMA_VERSION,
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
            "elapsed_s": time.time() - t_start,
            "provenance": provenance(),
            "rows": json_rows,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {len(json_rows)} rows to {args.json}", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
