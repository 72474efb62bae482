"""Device programs launched per selection call: the program's ``launches``
counter (``repro.obs.counters``: every jitted program call and eager ``jnp``
op of ``engine.simulate_and_select`` and the pool drivers) over its
``select_calls``, in this process. Every call of a cell, warm-up and window
alike, has the same shape, so this is the traced window's count per call;
on one chip it equals the first chip's ``XLA Modules`` events per call.
Nothing to read (None) where the program has no counters."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters.snapshot()
    return c["launches"] / c["select_calls"] if c.get("select_calls") else None
