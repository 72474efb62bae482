"""Device-resident flight recorder.

The capture path lives INSIDE the jitted ``lax.scan``s of the three
engines (``fast_sim`` pool, ``fleet`` contention, ``selector``/``engine``
selection) as extra stacked scan outputs — no host callbacks on the hot
path. Everything rides behind a static ``collect=`` flag: with
``collect=False`` (the default everywhere) the traced program is the exact
program shipped before this package existed (bitwise pin, enforced by
tests/test_telemetry.py and the forced-4-device subprocess parity tests).

Host side:

* :mod:`repro.obs.frame` — the ``TelemetryFrame`` view over the ``tel_*``
  keys the engines emit (telemetry travels as flat dict keys so the
  scatter-merge / shard_map / reorder plumbing needs no special cases);
* :mod:`repro.obs.ledger` — folds frames into structured, JSON-serializable
  metric reports (cost decomposition reconciled against reported
  utilities, preemption counts, fleet starvation incidence, selector
  convergence curves);
* :mod:`repro.obs.report` — renders a ledger as a textual dashboard;
* :mod:`repro.obs.counters` — the operator's view of transfer and dispatch
  load: always-on process-wide counts of the host arrays (and bytes) the
  selection path hands the device, the results it copies back and the
  device programs it launches. Take :func:`counters.snapshot` before and
  after the calls of interest and read the difference. Where the time of
  each step goes is in the ``sched.*`` profiler spans of ``core.engine``
  and ``core.fast_sim``, recorded only while a capture runs.
"""
from repro.obs.frame import (
    FALLBACK_KEYS,
    FLEET_KEYS,
    SLOT_KEYS,
    TEL_PREFIX,
    TelemetryFrame,
    frame_from_out,
    has_telemetry,
)
from repro.obs.ledger import (
    SCHEMA_VERSION,
    cost_reconciliation,
    fallback_events,
    fleet_ledger,
    grid_ledger,
    pool_ledger,
    selection_ledger,
)
from repro.obs.report import render

__all__ = [
    "TEL_PREFIX",
    "SLOT_KEYS",
    "FLEET_KEYS",
    "FALLBACK_KEYS",
    "fallback_events",
    "TelemetryFrame",
    "frame_from_out",
    "has_telemetry",
    "SCHEMA_VERSION",
    "cost_reconciliation",
    "pool_ledger",
    "fleet_ledger",
    "selection_ledger",
    "grid_ledger",
    "render",
]
