"""Host time of the forecast prep per call (ms): the harness's span around
the program's batched window gather and noisy forecast stack."""


def read(ctx):
    spans = ctx.trace.spans.get("bench.prep", [])
    if not spans:
        return None
    return sum(b - a for a, b in spans) / len(spans) / 1e6
