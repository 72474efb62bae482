"""Kilobytes (1e3 bytes) handed from host to device per selection call: the
program's ``h2d_bytes`` counter (``repro.obs.counters``; the host arrays'
``nbytes``) over its ``select_calls``, in this process; every call of a cell
has the same shape. None where the program has no counters."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters.snapshot()
    return (c.get("h2d_bytes", 0) / 1e3 / c["select_calls"]
            if c.get("select_calls") else None)
