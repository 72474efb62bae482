"""Share of the fleet program's AHAP-rule rows whose result is thrown away
(%): the program's ``fleet_rows_padded`` counter (``repro.obs.counters``,
counted in ``core.fleet``: rows of another kind, masked, and the sharded
layout's pad rows) over its ``fleet_rows``, process totals. Nothing to
read (None) where the program does not count fleet rows."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters.snapshot()
    if not c.get("fleet_rows"):
        return None
    return 100.0 * c.get("fleet_rows_padded", 0) / c["fleet_rows"]
