"""Process-wide transfer and dispatch counters of the selection path and
the fleet engine.

Monotone integers, always on (an integer add costs less than a profiler
span), counted where the work happens in ``core.engine``, the
``core.fast_sim`` pool drivers, ``core.predictor`` and ``core.fleet``:

* ``h2d_arrays`` / ``h2d_bytes`` -- host (numpy) arrays handed to the
  device: staged inputs, lane arguments, numpy leaves passed into a jitted
  program (the scatter-merge's pool order is a constant of its program
  and is not sent); bytes are the host array's ``nbytes``;
* ``d2h_arrays`` / ``d2h_bytes`` -- device results copied to the host;
* ``launches`` -- device programs run: every jitted program call and every
  eager ``jnp`` op;
* ``select_calls`` -- calls of ``engine.simulate_and_select``, to read the
  others per call;
* ``forecast_rows`` / ``forecast_rows_batched`` -- rows of noisy forecast
  drawn by ``predictor.noisy_matrix_batch``, and those of them whose noise
  generators were seeded in its one batched pass;
* ``fleet_calls`` -- calls of ``fleet.simulate_fleet[_sharded]``;
* ``fleet_rows`` / ``fleet_rows_padded`` -- the rows a fleet call's AHAP
  rule computes (every row of its layout, pads included), and those of
  them whose result is thrown away: jobs of another kind (masked) and the
  sharded layout's pad rows;
* ``fleet_cells`` / ``fleet_cells_live`` -- the (row, slot) cells a fleet
  call's scan computes, and those inside a real job's live window
  ``[arrival, arrival + deadline)`` (a job that finishes early still
  counts its whole window: the inputs alone say this).

Read them as deltas of :func:`snapshot` around the calls of interest. On
one device ``launches`` equals the programs a device trace shows run; on a
mesh of several, an eager op on a sharded array may run more than one
program, so there it is a lower bound. Not counted: the forecast prep's
``prep_backend="jax"`` programs and a fresh selector state
(``simulate_and_select(state=None)``).
"""
from __future__ import annotations

import threading
from typing import Dict

import jax
import numpy as np

_COUNTS: Dict[str, int] = {}
_LOCK = threading.Lock()


def add(name: str, n: int = 1) -> None:
    with _LOCK:
        _COUNTS[name] = _COUNTS.get(name, 0) + n


def snapshot() -> Dict[str, int]:
    """A copy of every counter's current value."""
    with _LOCK:
        return dict(_COUNTS)


def h2d(tree, times: int = 1) -> None:
    """Count the host arrays among ``tree``'s leaves as handed to the device
    ``times`` times (device arrays and python values are not counted)."""
    leaves = ((tree,) if isinstance(tree, np.ndarray)
              else jax.tree_util.tree_leaves(tree))
    n = b = 0
    for x in leaves:
        if isinstance(x, (np.ndarray, np.generic)):
            n += 1
            b += x.nbytes
    if n:
        with _LOCK:
            _COUNTS["h2d_arrays"] = _COUNTS.get("h2d_arrays", 0) + n * times
            _COUNTS["h2d_bytes"] = _COUNTS.get("h2d_bytes", 0) + b * times


def launch(fn, *args):
    """Run the jitted program ``fn`` on ``args``, counting one launch and
    the host arrays among its arguments."""
    h2d(args)
    add("launches")
    return fn(*args)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``, counted as a device-to-host copy when ``x`` is a
    device array."""
    out = np.asarray(x)
    if isinstance(x, jax.Array):
        with _LOCK:
            _COUNTS["d2h_arrays"] = _COUNTS.get("d2h_arrays", 0) + 1
            _COUNTS["d2h_bytes"] = _COUNTS.get("d2h_bytes", 0) + out.nbytes
    return out
