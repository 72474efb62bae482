"""Device time per call (ms) of the prediction-free lanes' scan: every run of the
program ``jit__pool_jobs_cheap`` on the first chip, over the calls traced."""

MODULE = "_pool_jobs_cheap"


def read(ctx):
    tr = ctx.trace
    if not tr.devices or not tr.calls():
        return None
    s = tr.module_s(tr.devices[0], MODULE)
    return s * 1e3 / tr.calls() if s > 0 else None
