"""Share of forecast rows whose noise generators were seeded in one batched
pass (%): the program's ``forecast_rows_batched`` counter
(``repro.obs.counters``, counted in ``core.predictor.noisy_matrix_batch``)
over its ``forecast_rows``, in this process. Every call of a cell has the
same number of rows, so this is the traced window's share. Nothing to read
(None) where the program does not count forecast rows."""


def read(ctx):
    try:
        from repro.obs import counters
    except ImportError:
        return None
    c = counters.snapshot()
    if not c.get("forecast_rows"):
        return None
    return 100.0 * c.get("forecast_rows_batched", 0) / c["forecast_rows"]
