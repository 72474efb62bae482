"""Device time per call (ms) of the fleet program: every run of
``jit__fleet_call`` (one device) or ``jit_sharded_fleet_scan`` (a job
mesh) on the first chip, over the calls traced."""

MODULES = ("_fleet_call", "sharded_fleet_scan")


def read(ctx):
    tr = ctx.trace
    if not tr.devices or not tr.calls():
        return None
    s = sum(tr.module_s(tr.devices[0], m) for m in MODULES)
    return s * 1e3 / tr.calls() if s > 0 else None
