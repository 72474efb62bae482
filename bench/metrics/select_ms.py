"""Device time per call (ms) of utility normalisation and the EG scan: every run of the
program ``jit__normalize_and_scan`` on the first chip, over the calls traced."""

MODULE = "_normalize_and_scan"


def read(ctx):
    tr = ctx.trace
    if not tr.devices or not tr.calls():
        return None
    s = tr.module_s(tr.devices[0], MODULE)
    return s * 1e3 / tr.calls() if s > 0 else None
