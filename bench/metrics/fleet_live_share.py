"""Share of the fleet scan's (row, slot) cells that lie in a real job's
live window (%): the program's ``fleet_cells_live`` counter
(``repro.obs.counters``, counted in ``core.fleet``) over its
``fleet_cells``, process totals. Nothing to read (None) where the program
does not count fleet cells."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters.snapshot()
    if not c.get("fleet_cells"):
        return None
    return 100.0 * c.get("fleet_cells_live", 0) / c["fleet_cells"]
