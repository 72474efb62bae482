"""Kilobytes (1e3 bytes) copied from device to host per selection call: the
program's ``d2h_bytes`` counter (``repro.obs.counters``: each result's
``np.asarray`` at the end of ``engine.simulate_and_select``) over its
``select_calls``, in this process; every call of a cell has the same shape.
None where the program has no counters."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters.snapshot()
    return (c.get("d2h_bytes", 0) / 1e3 / c["select_calls"]
            if c.get("select_calls") else None)
