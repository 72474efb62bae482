"""Host arrays handed to the device per selection call: the program's
``h2d_arrays`` counter (``repro.obs.counters``: staged inputs, lane
arguments, numpy leaves passed into jitted programs, the scatter-merge's
index array) over its ``select_calls``, in this process; every call of a
cell has the same shape. None where the program has no counters."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters.snapshot()
    return (c.get("h2d_arrays", 0) / c["select_calls"]
            if c.get("select_calls") else None)
